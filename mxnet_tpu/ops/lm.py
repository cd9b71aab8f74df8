"""Language-model layer operators: RMSNorm, RotaryEmbedding, SwiGLU,
GatedShortConv and SparseExperts.

Beyond the reference's 2017 op set: what a sparse decoder-only language
model (``models/lfm2_moe.py``) needs of a Symbol graph, each with shape
inference so that ``Module``, ``simple_bind`` and the JSON round trip see
it.  Statistics (norms, the router) are computed in float32 whatever the
compute dtype; matrix products take their inputs' dtype and accumulate in
float32.  ``models/lfm2_moe_reference.py`` is the plain float32 statement
of the same equations, and ``tests/test_lfm2_moe.py`` holds each op to it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .registry import register, register_simple
from .nn import _complete


# ---------------------------------------------------------------------------
# RMSNorm: y = x / sqrt(mean(x^2) + eps) * gamma over the last axis
# ---------------------------------------------------------------------------

def _rms_norm_apply(attrs, inputs, is_train, rng):
    x, gamma = inputs
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) +
                          float(attrs.get('eps', 1e-5)))
    return [(xf * scale * gamma.astype(jnp.float32)).astype(x.dtype)], {}


def _rms_norm_complete(attrs, in_shapes):
    if in_shapes[0] is not None:
        _complete(in_shapes, 1, (in_shapes[0][-1],))
    return in_shapes


register('RMSNorm', _rms_norm_apply,
         input_names=lambda attrs: ['data', 'gamma'],
         num_outputs=lambda attrs: 1,
         complete_shapes=_rms_norm_complete,
         attr_defaults={'eps': 1e-5}, hint='rmsnorm',
         doc='Root-mean-square norm over the last axis, statistics in '
             'float32.')


# ---------------------------------------------------------------------------
# RotaryEmbedding over (..., T, D) at positions 0..T-1, half-split pairing
# ---------------------------------------------------------------------------

def _rotary(x, theta=10000.0):
    t, d = x.shape[-2:]
    half = d // 2
    inv_freq = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) *
                                2.0 / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


register_simple('RotaryEmbedding', _rotary,
                attr_defaults={'theta': 10000.0}, hint='rotary',
                doc='Rotary position embedding of (..., T, D) at positions '
                    '0..T-1; element i turns with element i + D/2.')


# ---------------------------------------------------------------------------
# SwiGLU: silu(gate) * up, the gated feed-forward's elementwise middle
# ---------------------------------------------------------------------------

register_simple('SwiGLU', lambda gate, up: jax.nn.silu(gate) * up,
                ninputs=2, input_names=['gate', 'up'], hint='swiglu',
                doc='silu(gate) * up.')


# ---------------------------------------------------------------------------
# GatedShortConv: what lies between the two projections of a gated short
# convolution.  data (N, T, 3C) = [B, C, u]; g = B * u; c_t = sum_j
# k_j g_{t-j} per channel (depthwise, causal, zeros before the start);
# output C * c.  weight (C, taps).  Written as shifted multiply-adds, which
# XLA fuses into one pass over the activations: a grouped convolution with
# one channel a group has no work for the MXU.
# ---------------------------------------------------------------------------

def _gated_short_conv_apply(attrs, inputs, is_train, rng):
    bcu, kernel = inputs
    b, c, u = jnp.split(bcu, 3, axis=-1)
    g = b * u
    t = g.shape[1]
    mixed = kernel[:, 0] * g
    for j in range(1, kernel.shape[1]):
        mixed = mixed + kernel[:, j] * \
            jnp.pad(g, ((0, 0), (j, 0), (0, 0)))[:, :t]
    return [c * mixed], {}


def _gated_short_conv_complete(attrs, in_shapes):
    if in_shapes[0] is not None:
        _complete(in_shapes, 1, (in_shapes[0][-1] // 3, int(attrs['kernel'])))
    return in_shapes


register('GatedShortConv', _gated_short_conv_apply,
         input_names=lambda attrs: ['data', 'weight'],
         num_outputs=lambda attrs: 1,
         complete_shapes=_gated_short_conv_complete,
         attr_defaults={'kernel': 3}, hint='gatedshortconv',
         doc='Gate, causal depthwise convolution along T, gate: '
             '(N, T, 3C) -> (N, T, C).')


# ---------------------------------------------------------------------------
# SparseExperts: this device's share of a layer of routed experts.
#
# The router scores every token against all ``num_experts`` experts
# (sigmoid, in float32), chooses ``experts_per_tok`` by score plus the
# selection bias, and weighs them by their scores normalised over the
# chosen.  The op is told which experts it holds (``experts_held`` =
# (first, count)); it sorts the assignments by expert, those that landed
# on other devices' experts last, takes the rows at the head of that order
# into a buffer, runs three grouped matrix products over the buffer, and
# combines.  What the absent experts would have added is left out: under
# expert parallelism their devices add it.
#
# The buffer is what a device with static shapes receives into: a ladder of
# buffers (``_room``), two and four times the share a balanced router sends
# to the held experts and one with room for every assignment, each expert's
# rows together and starting on a multiple of ``align`` rows.  A step runs
# in the smallest that holds what arrived, by the branches of one
# ``switch``: no token is ever dropped whatever the imbalance, and
# ``expert_count`` says how often the last rung was taken.  The products'
# groups are the experts' own rows rounded up to ``align``: a group's first
# row is a tile's first row, no tile is visited for two experts, and the
# tiles past the last group are not visited at all, so the products cost
# what arrived, rounded up to a tile an expert.  Every copy between the
# tokens and the buffer is made from the buffer's side, a row of the rung
# at a time: a token's row taken for each of the buffer's rows, and each
# filled row of the buffer added into its token's row (``_collect``); so
# the copies, the gate and the mask cost the rung.  The rows past the last
# group are whatever the device's memory held: ``filled`` masks them before
# anything weighs them and keeps them out of the sum.
# ---------------------------------------------------------------------------

def _collect(rows, token, tokens):
    """Each of the ``tokens`` tokens' sum of the buffer's ``rows`` that hold
    an assignment of its: ``token`` (rows,) is each row's token, or
    ``tokens`` for a row that holds nothing, which is added nowhere."""
    return jnp.zeros((tokens, rows.shape[-1]), rows.dtype) \
        .at[token].add(rows, mode='drop')


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _dispatch(x, token, tokens):
    """The rows of ``x`` (tokens, H) that the buffer's rows hold; a row
    that holds nothing takes the last."""
    return jnp.take(x, token, axis=0, mode='clip')


def _dispatch_fwd(x, token, tokens):
    return _dispatch(x, token, tokens), token


def _dispatch_bwd(tokens, token, g):
    return (_collect(g, token, tokens), None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _combine(ys, token, tokens):
    """Each token's sum over its assignments of the buffer's rows."""
    return _collect(ys, token, tokens)


def _combine_fwd(ys, token, tokens):
    return _combine(ys, token, tokens), token


def _combine_bwd(tokens, token, g):
    return (jnp.take(g, token, axis=0, mode='clip'), None)


_combine.defvjp(_combine_fwd, _combine_bwd)


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs`` (M, K) rows in consecutive groups against ``rhs`` (G, K, N),
    group ``g`` with ``rhs[g]``.  ``jax.lax.ragged_dot``: XLA's own grouped
    product on the TPU, with its transposes for both gradients."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=lhs.dtype)


def route(x, router, bias, k, normalise, scaling):
    """Chosen experts (T, k) and their float32 weights (T, k)."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32).T,
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    weights = jnp.take_along_axis(scores, chosen, axis=1)
    if normalise:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-6)
    return chosen, weights * scaling


def _held(attrs):
    first, count = attrs['experts_held']
    return int(first), int(count)


def _room(assignments, held, experts):
    """``(rooms, four, align)``: the rows of the buffers the held experts'
    products may run over, smallest first; of the one among them that is
    four times what a balanced router sends to ``held`` of ``experts``; and
    the multiple of rows each expert's rows start on.  The ladder is two
    such shares, four, and one buffer that holds every assignment however
    they fall; a rung as large as that one is that one.  ``align`` is 512
    (a multiple of the grouped product's row tile on the TPU) or, for a
    small layer, what costs at most a quarter of the four-share buffer."""
    share = 4 * -(-assignments * held // experts)
    align = min(512, max(1, min(share, assignments) // (4 * held)))
    align = 1 << (align.bit_length() - 1)
    whole = -(-(assignments + held * align) // align) * align
    two, four = (min(_aligned(rows, align), whole)
                 for rows in (share // 2, share))
    return tuple(sorted({two, four, whole})), four, align


def _aligned(sizes, align):
    """Each group's rows rounded up to a multiple of ``align``."""
    return -(-sizes // align) * align


def _rung(rooms, rows):
    """Which of ``rooms`` is the smallest that holds ``rows``."""
    return sum(rows > room for room in rooms[:-1])


def _buffered(room, align, k, floats, ints):
    """The held experts' part of the layer over a buffer of ``room`` rows
    that holds every assignment that landed on them."""
    x, weights, w1, w3, w2 = floats
    order, group_sizes = ints
    count, tokens = group_sizes.shape[0], x.shape[0]
    with jax.named_scope('dispatch'):
        # expert e has ``padded[e]`` rows of the buffer, up to ``ends[e]``,
        # and fills the first ``group_sizes[e]``; ``shift[e]`` is how far
        # its first row lies past its first place in the sorted order
        padded = _aligned(group_sizes, align)
        ends = jnp.cumsum(padded)
        shift = (ends - padded) - (jnp.cumsum(group_sizes) - group_sizes)
        row = jnp.arange(room)
        expert = jnp.minimum((row[:, None] >= ends[None, :]).sum(axis=1),
                             count - 1)
        filled = row - (ends - padded)[expert] < group_sizes[expert]
        assignment = jnp.take(order, row - shift[expert], mode='clip')
        token = jnp.where(filled, assignment // k, tokens)
        xs = _dispatch(x, token, tokens)
    with jax.named_scope('experts'):
        # the rows past ``ends[-1]`` are in no group: unvisited, unwritten
        hidden = jax.nn.silu(grouped_matmul(xs, w1, padded)) * \
            grouped_matmul(xs, w3, padded)
        ys = grouped_matmul(hidden, w2, padded)
    with jax.named_scope('combine'):
        gate = jnp.take(weights.reshape(-1), assignment)[:, None]
        # masked before it is weighed: what a row that holds nothing reads
        # must not reach the gate's gradient as 0 x anything
        ys = jnp.where(filled[:, None], ys.astype(jnp.float32), 0) * gate
        return _combine(ys.astype(x.dtype), token, tokens)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _on_the_ladder(rooms, align, k, rung, floats, ints):
    """``_buffered`` over ``rooms[rung]`` rows.  The backward pass computes
    the taken branch's forward pass again inside its own branch, so that
    nothing a branch keeps has to exist for every rung."""
    return jax.lax.switch(
        rung, [functools.partial(_buffered, room, align, k)
               for room in rooms], floats, ints)


def _on_the_ladder_fwd(rooms, align, k, rung, floats, ints):
    return _on_the_ladder(rooms, align, k, rung, floats, ints), \
        (rung, floats, ints)


def _on_the_ladder_bwd(rooms, align, k, res, g):
    rung, floats, ints = res

    def backward(room, floats, ints, g):
        return jax.vjp(lambda *f: _buffered(room, align, k, f, ints),
                       *floats)[1](g)

    return (None,
            jax.lax.switch(rung, [functools.partial(backward, room)
                                  for room in rooms], floats, ints, g),
            None)


_on_the_ladder.defvjp(_on_the_ladder_fwd, _on_the_ladder_bwd)


def _sparse_experts_apply(attrs, inputs, is_train, rng):
    x, router, w1, w3, w2, bias, _, count_so_far = inputs
    k = int(attrs['experts_per_tok'])
    first, count = _held(attrs)
    with jax.named_scope('router'):
        chosen, weights = route(x, router, bias, k,
                                bool(attrs['norm_topk_prob']),
                                float(attrs['routed_scaling_factor']))
    with jax.named_scope('dispatch'):
        local = chosen.reshape(-1) - first
        mine = (local >= 0) & (local < count)
        # absent experts' assignments sort last
        key = jnp.where(mine, local, count)
        order = jnp.argsort(key, stable=True)
        group_sizes = jnp.bincount(key, length=count + 1)[:count] \
            .astype(jnp.int32)
    rooms, _, align = _room(chosen.size, count, int(attrs['num_experts']))
    floats = (x, weights, w1, w3, w2)
    ints = (order, group_sizes)
    if len(rooms) > 1:
        rung = _rung(rooms, _aligned(group_sizes, align).sum())
        y = _on_the_ladder(rooms, align, k, rung, floats, ints)
        last = (rung == len(rooms) - 1).astype(jnp.float32)
    else:
        y = _buffered(rooms[0], align, k, floats, ints)
        last = jnp.float32(0)
    load = group_sizes.astype(jnp.float32)
    held = jnp.sum(mine).astype(jnp.float32)
    step = jnp.stack([jnp.float32(chosen.size), held, held - load.sum(),
                      last])
    return [y], {'expert_load': load,
                 'expert_count': count_so_far.astype(jnp.float32) + step}


def _sparse_experts_counters(now, before, attrs, in_shapes):
    """The layer's counts since the last drain into the registry, how
    uneven the last step's load was over the experts held, and of the
    buffer the last step ran in: its rows over the four-share buffer's, and
    the share of them that the products visited."""
    from .. import instrument
    count = now['expert_count'] - (before['expert_count'] if before else 0)
    instrument.inc('moe.assignments', int(count[0]))
    instrument.inc('moe.assignments_held', int(count[1]))
    instrument.inc('moe.tokens_dropped', int(count[2]))
    instrument.inc('moe.steps_over_capacity', int(count[3]))
    load = now['expert_load']
    if load.sum() > 0:
        instrument.observe_hist('moe.load_max_over_mean',
                                float(load.max() / load.mean()))
    rooms, four, align = _room(
        in_shapes[0][0] * int(attrs['experts_per_tok']), load.shape[0],
        int(attrs['num_experts']))
    visited = float(_aligned(load, align).sum())
    room = rooms[_rung(rooms, visited)]
    instrument.observe_hist('moe.rows_visited_share', visited / room)
    instrument.observe_hist('moe.rows_copied_share', room / four)


def _sparse_experts_complete(attrs, in_shapes):
    if in_shapes[0] is None:
        return in_shapes
    hidden = in_shapes[0][-1]
    _, count = _held(attrs)
    _complete(in_shapes, 1, (int(attrs['num_experts']), hidden))
    if in_shapes[2] is not None:
        width = in_shapes[2][2]
    elif attrs.get('expert_hidden') is not None:
        width = int(attrs['expert_hidden'])
    else:
        return in_shapes
    _complete(in_shapes, 2, (count, hidden, width))
    _complete(in_shapes, 3, (count, hidden, width))
    _complete(in_shapes, 4, (count, width, hidden))
    return in_shapes


def _sparse_experts_aux_shapes(attrs, in_shapes):
    return [(int(attrs['num_experts']),), (_held(attrs)[1],), (4,)]


register('SparseExperts', _sparse_experts_apply,
         input_names=lambda attrs: ['data', 'router_weight', 'w1_weight',
                                    'w3_weight', 'w2_weight'],
         num_outputs=lambda attrs: 1,
         aux_names=lambda attrs: ['expert_bias', 'expert_load',
                                  'expert_count'],
         aux_shape=_sparse_experts_aux_shapes,
         complete_shapes=_sparse_experts_complete,
         keep_dtype=('router_weight',),
         aux_counters=_sparse_experts_counters,
         attr_defaults={'num_experts': None, 'experts_held': None,
                        'experts_per_tok': 1, 'expert_hidden': None,
                        'norm_topk_prob': True,
                        'routed_scaling_factor': 1.0},
         hint='sparseexperts',
         doc='The held experts\' part of a routed SwiGLU expert layer: '
             'data (T, H) -> (T, H).  Auxiliary states: expert_bias '
             '(num_experts,), added to the scores for the choice only and '
             'never trained; expert_load (held,), the assignments each held '
             'expert received in the last step; expert_count (4,), running '
             'totals of assignments routed, assignments that landed on held '
             'experts, tokens dropped (always 0), and steps that sent the '
             'held experts more than any buffer but the last holds.  The '
             'buffer is a ladder of buffers, two and four times a balanced '
             'router\'s share and one for every assignment, of which a step '
             'takes the smallest that holds it; the copies into and out of '
             'it cost that buffer\'s rows, and the grouped products run over '
             'each held expert\'s assignments rounded up to a row tile and '
             'over no other row of it.')
