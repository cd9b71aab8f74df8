"""Language-model layer operators: RMSNorm, RotaryEmbedding, SwiGLU,
GatedShortConv, SparseExperts, KimiDeltaAttention and Mamba2Mixer.

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

from . import pallas_kda
from .registry import keep, register, register_simple
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
# into a buffer, runs the experts' grouped matrix products (three for a
# gated expert, two for an ungated one) over the buffer, and
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
# what arrived, rounded up to a tile an expert.  The last rung runs the rows
# of the rung before it at a time (``_in_slices``), so that a buffer no
# balanced step ever takes costs that rung's memory and not, at 8 of 256
# experts held, sixteen times the first one's.  Every copy between the
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


def route(x, router, bias, k, normalise, scaling, eps=1e-6):
    """Chosen experts (T, k) and their float32 weights (T, k); ``eps`` is
    added to the chosen scores' sum before it divides them.  The choice and
    the chosen logits are kept by a mirror stage, and the weights are the
    sigmoid of the chosen logits (the chosen scores, value and gradient): so
    the backward pass needs neither the (T, experts) scores nor the product
    that made them, and runs no ``top_k`` again."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32).T,
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(jax.lax.stop_gradient(logits))
    _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    chosen = keep(chosen)
    weights = jax.nn.sigmoid(
        keep(jnp.take_along_axis(logits, chosen, axis=1)))
    if normalise:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + eps)
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


def _rows_within(ends, padded, first, room):
    """How many of each expert's ``padded`` rows of the buffer, which end at
    row ``ends``, lie among the ``room`` rows from row ``first``."""
    return jnp.clip(ends, first, first + room) - \
        jnp.clip(ends - padded, first, first + room)


def _buffered(room, align, k, floats, ints, first=None):
    """The held experts' part of the layer over a buffer of ``room`` rows
    that holds every assignment that landed on them; or, given ``first``,
    over the ``room`` rows of a larger buffer that start at row ``first``
    (a multiple of ``align``).  ``floats`` are the rows the experts take
    (tokens, H), the weights of ``route`` and the experts' matrices, ``w3``
    None for experts without a gate."""
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
        groups = padded
        if first is not None:
            row = row + first
            groups = _rows_within(ends, padded, first, room)
        expert = jnp.minimum((row[:, None] >= ends[None, :]).sum(axis=1),
                             count - 1)
        filled = row - (ends - padded)[expert] < group_sizes[expert]
        assignment = jnp.take(order, row - shift[expert], mode='clip')
        token = jnp.where(filled, assignment // k, tokens)
        xs = _dispatch(x, token, tokens)
    with jax.named_scope('experts'):
        # the rows past the last group are in no group: unvisited, unwritten
        if w3 is None:      # ungated: relu(x W1)^2 W2
            hidden = jnp.square(jax.nn.relu(grouped_matmul(xs, w1, groups)))
        else:
            hidden = jax.nn.silu(grouped_matmul(xs, w1, groups)) * \
                grouped_matmul(xs, w3, groups)
        ys = grouped_matmul(hidden, w2, groups)
    with jax.named_scope('combine'):
        gate = jnp.take(weights.reshape(-1), assignment)[:, None]
        # masked before it is weighed: what a row that holds nothing reads
        # must not reach the gate's gradient as 0 x anything
        ys = jnp.where(filled[:, None], ys.astype(jnp.float32), 0) * gate
        return _combine(ys.astype(x.dtype), token, tokens)


def _in_slices(room, rows, align, k, floats, ints):
    """``_buffered`` over a buffer of ``room`` rows, ``rows`` of them at a
    time, each slice computed again in the backward pass: the ladder's last
    rung, whose buffer holds every assignment however they fall, then costs
    the memory of the rung before it and not its own rows (at 8 of 256
    experts held, sixteen times the first rung's) in a step that never
    takes it."""
    def one(total, first):
        return total + _buffered(rows, align, k, floats, ints, first), None
    total, _ = jax.lax.scan(
        jax.checkpoint(one), jnp.zeros_like(floats[0]),
        jnp.arange(-(-room // rows), dtype=jnp.int32) * rows)
    return total


def _rungs(rooms, align, k):
    """The ladder's branches, smallest first."""
    fns = [functools.partial(_buffered, room, align, k) for room in rooms]
    if len(rooms) > 1 and rooms[-1] > rooms[-2]:
        fns[-1] = functools.partial(_in_slices, rooms[-1], rooms[-2], align,
                                    k)
    return fns


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _on_the_ladder(rooms, align, k, rung, floats, ints):
    """``_buffered`` over ``rooms[rung]`` rows.  The backward pass computes
    the taken branch's forward pass again inside its own branch, so that
    nothing a branch keeps has to exist for every rung."""
    return jax.lax.switch(rung, _rungs(rooms, align, k), floats, ints)


def _on_the_ladder_fwd(rooms, align, k, rung, floats, ints):
    return _on_the_ladder(rooms, align, k, rung, floats, ints), \
        (rung, floats, ints)


def _on_the_ladder_bwd(rooms, align, k, res, g):
    rung, floats, ints = res

    def backward(fn, floats, ints, g):
        return jax.vjp(lambda *f: fn(f, ints), *floats)[1](g)

    return (None,
            jax.lax.switch(rung, [functools.partial(backward, fn)
                                  for fn in _rungs(rooms, align, k)],
                           floats, ints, g),
            None)


_on_the_ladder.defvjp(_on_the_ladder_fwd, _on_the_ladder_bwd)


def _sparse_experts_inputs(attrs):
    """The op's inputs by name: the rows the experts take come apart from
    the data the router scores under ``latent_input``, and experts without
    a gate (``expert_form`` ``relu2``) have no ``w3_weight``."""
    form = attrs.get('expert_form', 'swiglu')
    if form not in ('swiglu', 'relu2'):
        raise ValueError('SparseExperts: expert_form is swiglu or relu2, '
                         'not %r' % (form,))
    return ['data'] + ['latent'] * bool(attrs.get('latent_input')) + \
        ['router_weight', 'w1_weight'] + \
        ['w3_weight'] * (form == 'swiglu') + ['w2_weight']


def _sparse_experts_apply(attrs, inputs, is_train, rng):
    names = _sparse_experts_inputs(attrs)
    given = dict(zip(names, inputs))
    bias, _, count_so_far = inputs[len(names):]
    x, router = given['data'], given['router_weight']
    k = int(attrs['experts_per_tok'])
    first, count = _held(attrs)
    with jax.named_scope('router'):
        chosen, weights = route(x, router, bias, k,
                                bool(attrs['norm_topk_prob']),
                                float(attrs['routed_scaling_factor']),
                                float(attrs['topk_eps']))
    with jax.named_scope('dispatch'):
        local = chosen.reshape(-1) - first
        mine = (local >= 0) & (local < count)
        # absent experts' assignments sort last
        key = jnp.where(mine, local, count)
        order = keep(jnp.argsort(key, stable=True))
        group_sizes = keep(jnp.bincount(key, length=count + 1)[:count]
                           .astype(jnp.int32))
    rooms, _, align = _room(chosen.size, count, int(attrs['num_experts']))
    floats = (given.get('latent', x), weights, given['w1_weight'],
              given.get('w3_weight'), given['w2_weight'])
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
    at = {name: i for i, name in enumerate(_sparse_experts_inputs(attrs))}
    _, count = _held(attrs)
    _complete(in_shapes, at['router_weight'],
              (int(attrs['num_experts']), in_shapes[0][-1]))
    rows = in_shapes[at.get('latent', 0)]
    if rows is None:
        return in_shapes
    hidden = rows[-1]
    if in_shapes[at['w1_weight']] is not None:
        width = in_shapes[at['w1_weight']][2]
    elif attrs.get('expert_hidden') is not None:
        width = int(attrs['expert_hidden'])
    else:
        return in_shapes
    for name in ('w1_weight', 'w3_weight'):
        if name in at:
            _complete(in_shapes, at[name], (count, hidden, width))
    _complete(in_shapes, at['w2_weight'], (count, width, hidden))
    return in_shapes


def _sparse_experts_aux_shapes(attrs, in_shapes):
    return [(int(attrs['num_experts']),), (_held(attrs)[1],), (4,)]


register('SparseExperts', _sparse_experts_apply,
         input_names=_sparse_experts_inputs,
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
                        'routed_scaling_factor': 1.0,
                        'expert_form': 'swiglu', 'latent_input': False,
                        'topk_eps': 1e-6},
         hint='sparseexperts',
         doc='The held experts\' part of a layer of routed experts: data '
             '(T, H) -> (T, H).  An expert is silu(x W1) * (x W3) then W2 '
             '(expert_form swiglu, the default) or relu(x W1)^2 then W2 with '
             'no w3_weight input (relu2).  Under latent_input the experts '
             'take the rows of a second input, latent (T, L), and give (T, '
             'L), while the router scores data: experts that live in a '
             'latent narrower than the model.  topk_eps is added to the '
             'chosen scores\' sum before it divides them.  Auxiliary states: '
             'expert_bias '
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


# ---------------------------------------------------------------------------
# KimiDeltaAttention: what lies between the projections of a Kimi Delta
# Attention layer (Kimi Linear, arXiv:2510.26692).  For a token t and a head,
# d channels a head:
#
#   q = l2norm(silu(conv(query))) / sqrt(d),  k = l2norm(silu(conv(key))),
#   v = silu(conv(value))                  (``conv``: causal, depthwise)
#   g = -exp(A_log[head]) * softplus(decay + dt_bias)   log-decay, a channel
#   beta = sigmoid(beta)                                one a head
#   S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
#   o_t = S_t^T q_t,   output RMSNorm_d(o_t) * sigmoid(gate)
#
# with S_0 = 0 at the start of every sequence.  One scope, ``scan``, and
# under it ``conv``, ``gates`` and ``out_gate``: everything runs a segment of
# chunks at a time inside one outer scan (``_segments``), so that nothing as
# long as the sequence exists but the layer's projections, its output and
# their gradients.  The recurrence runs in chunks of ``chunk_size`` tokens
# (``delta_rule_chunked``): with G the decay summed from the chunk's
# start, u_t = beta_t (v_t - (Diag(exp(g_t)) S_{t-1})^T k_t) solves
# (I + Diag(beta) A) U = Diag(beta) (V - (K e^G) S_0),  A_ti = sum_d k_t k_i
# e^(G_t - G_i) for i < t, whose inverse T (a triangular solve, the WY
# representation of the chunk's product of Householder-like factors) is made
# for all chunks at once; a ``lax.scan`` carries the state:
#   U = T V - (T K e^G) S_0,  O = (Q e^G) S_0 + B U,  B_ti = sum_d q_t k_i
#   e^(G_t - G_i) for i <= t,  S_C = Diag(e^(G_C)) S_0 + (K e^(G_C - G))^T U.
# Every e^(G_t - G_i) is a product of two factors through the sum at the
# start of t's sub-block of ``KDA_SUB`` tokens, so that both products of a
# chunk are matrix products: the factor of t is at most 1, that of an i in an
# earlier sub-block too, and that of an i in t's own sub-block is at most
# e^(-KDA_SUB x floor) because a token's log-decay of a channel is held to
# ``KDA_DECAY_FLOOR`` at least, which is the one place where the chunked form
# departs from the recurrence: a decay under e^-10 = 4.5e-5 a token is taken
# as that (``count`` says how many of the log-decays were; a trained model's
# rates times its steps stay far over it).  Sub-blocks of 16 would halve the
# factors made and want a floor of -5, a decay of 0.0067, which drawn weights
# reach in a tenth of the tokens of their fastest channels: on the chip that
# read as an error of the model (PERF.md section 6, PR 34).  Sums are
# float32; the products take the inputs' dtype and accumulate in float32; the
# triangular algebra is float32 at the default precision of a float32 product
# (on the TPU one bf16 pass: I - N is exact, and what the pass rounds are the
# powers of N from the second on, which the cast of T to the inputs' dtype
# rounds as much).
#
# Two forms of one algorithm.  On a TPU, at head widths a multiple of 128,
# chunks a multiple of 16 and in float32 or bfloat16 (``_rule_in_kernel``: a
# static predicate on what the trace sees, no knob), a segment's rule runs in
# the Pallas kernels of ``pallas_kda``: one call forward, one backward, a
# chunk's parts, the inverse and the carried state in VMEM, the inverse
# differentiated as an inverse.  Anywhere else (off the TPU, other shapes,
# ``MXTPU_DISABLE_PALLAS``) it runs in the jnp form below (``_chunk_parts``,
# ``_chunk_step``, ``_carry_state``, differentiated by JAX), which is also
# the tests' second oracle beside the token-by-token recurrence.  The
# kernels round where the jnp form on the TPU rounds and nowhere else;
# ``count`` says through ``kda.chunks_in_kernel`` which form ran.
# ---------------------------------------------------------------------------

KDA_SUB = 8
KDA_SEGMENT = 16
# e^(8 x 10) = e^80 is under the largest number float32 and bf16 hold (e^88)
KDA_DECAY_FLOOR = -10.0


def causal_conv_silu(x, kernel, lead, bias=None):
    """``silu`` of the causal depthwise convolution of ``x`` (N, lead + T, C)
    along T: tap ``j`` of ``kernel`` (C, taps) weighs the token ``j`` back;
    the first ``lead`` rows of ``x`` (at least taps - 1; zeros before a
    sequence's start) are the tokens before the T that get an output;
    ``bias`` (C,), if given, is added before the ``silu``.  Shifted
    multiply-adds, as ``GatedShortConv``."""
    t = x.shape[1] - lead
    mixed = kernel[:, 0] * x[:, lead:]
    for j in range(1, kernel.shape[1]):
        mixed = mixed + kernel[:, j] * x[:, lead - j:lead - j + t]
    if bias is not None:
        mixed = mixed + bias
    return jax.nn.silu(mixed.astype(jnp.float32)).astype(x.dtype)


def _l2norm(x):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) +
                              1e-6)


def _unit_lower_inverse(lower, block):
    """The inverse of ``I + N`` for ``N`` = ``lower`` (..., C, C) strictly
    lower triangular.  A nilpotent's inverse is a finite product, (I + N)^-1
    = (I - N)(I + N^2)(I + N^4)..., taken in two steps so that no power
    passes ``block``: first of N's diagonal blocks of ``block`` rows, D,
    then of D^-1 (N - N_D), which is nilpotent over the C / block blocks."""
    c = lower.shape[-1]
    eye = jnp.eye(c, dtype=lower.dtype)

    def neumann(n, size):
        out, power = eye - n, n
        for _ in range(max(0, (size - 1).bit_length() - 1)):
            power = jnp.matmul(power, power)
            out = out + jnp.matmul(out, power)
        return out

    same = (jnp.arange(c)[:, None] // block) == (jnp.arange(c)[None, :] //
                                                 block)
    inside = neumann(jnp.where(same, lower, 0.0), block)
    if block >= c:
        return inside
    over = neumann(jnp.matmul(inside, jnp.where(same, 0.0, lower)),
                   c // block)
    return jnp.matmul(over, inside)


def _chunk_parts(q, k, v, g, beta):
    """What the scan over chunks takes, for every chunk at once.  ``q``,
    ``k``, ``v``, ``g`` (..., C, d) and ``beta`` (..., C) of one chunk each
    (``g``, at or over ``KDA_DECAY_FLOOR``, and ``beta`` float32).  Returns
    ``W = T K e^G``, ``U0 = T V``, ``Q e^G``, ``B``, ``K e^(G_C - G)`` in the
    inputs' dtype and ``e^(G_C)`` in float32."""
    dtype = q.dtype
    c, d = q.shape[-2:]
    b = KDA_SUB                     # ``delta_rule_chunked`` makes c a multiple
    s = c // b
    lead = q.shape[:-2]
    total = jnp.cumsum(g, axis=-2)                       # G, (..., C, d)
    qf, kf = q.astype(jnp.float32), k.astype(jnp.float32)

    def blocks(x):
        return x.reshape(lead + (s, b, d))
    tb = blocks(total)
    # e^(G_t - G_i) as a product through the sum at the start of t's
    # sub-block: e^(G_t - start) is at most 1, and e^(start - G_i) at most 1
    # for an earlier sub-block's i and at most e^(-b x floor) for one of the
    # same sub-block, which float32 and bf16 both hold
    start = jnp.concatenate([jnp.zeros_like(tb[..., :1, -1, :]),
                             tb[..., :-1, -1, :]], axis=-2)  # (..., S, d)
    row = jnp.exp(tb - start[..., None, :])
    not_later = (jnp.arange(c)[None, :] < ((jnp.arange(s) + 1) * b)[:, None])
    col = jnp.where(
        not_later[..., None],
        jnp.exp(jnp.minimum(start[..., :, None, :] - total[..., None, :, :],
                            -b * KDA_DECAY_FLOOR)), 0.0) * \
        kf[..., None, :, :]                              # (..., S, C, d)
    col = col.astype(dtype)

    def decayed_products(x):
        return jnp.einsum('...sid,...sjd->...sij',
                          (blocks(x) * row).astype(dtype), col,
                          preferred_element_type=jnp.float32) \
            .reshape(lead + (c, c))
    lower = jnp.tril(jnp.ones((c, c), bool))
    a = jnp.where(jnp.tril(lower, -1), decayed_products(kf), 0.0)
    whole_b = jnp.where(lower, decayed_products(qf), 0.0)
    inverse = _unit_lower_inverse(beta[..., None] * a, b)
    t = (inverse * beta[..., None, :]).astype(dtype)
    decay = jnp.exp(total)
    w = jnp.matmul(t, (kf * decay).astype(dtype),
                   preferred_element_type=jnp.float32)
    u0 = jnp.matmul(t, v, preferred_element_type=jnp.float32)
    last = total[..., -1:, :]
    return (w.astype(dtype), u0.astype(dtype), (qf * decay).astype(dtype),
            whole_b.astype(dtype), (kf * jnp.exp(last - total)).astype(dtype),
            decay[..., -1, :])


def _chunk_step(state, part):
    """One chunk of the scan: the state it leaves and its outputs."""
    w, u0, q_decayed, b, k_rest, decay_last = part
    dtype = w.dtype
    carried = state.astype(dtype)
    u = (u0.astype(jnp.float32) - jnp.matmul(
        w, carried, preferred_element_type=jnp.float32)).astype(dtype)
    out = jnp.matmul(q_decayed, carried,
                     preferred_element_type=jnp.float32) + \
        jnp.matmul(b, u, preferred_element_type=jnp.float32)
    after = decay_last[..., :, None] * state + jnp.matmul(
        k_rest.swapaxes(-2, -1), u, preferred_element_type=jnp.float32)
    return after, out.astype(dtype)


def _carry_state(state, parts):
    """The scan over the chunks (axis 0 of every part) from ``state``
    (..., d_k, d_v), float32: the state after the last chunk and the
    outputs of every chunk, (chunks, ..., C, d_v) in the parts' dtype."""
    return jax.lax.scan(_chunk_step, state, parts)


def _rule_in_kernel(rows, d_k, d_v, c, dtype):
    """Whether a segment of ``rows`` tokens in chunks of ``c``, heads of
    ``d_k`` and ``d_v`` channels in ``dtype``, runs in the Pallas kernels
    (``pallas_kda``): on a TPU, or under the interpreter, and at shapes and
    a dtype they were written for; anything else takes the jnp form.  What
    the code can see when it is traced, and no knob."""
    return pallas_kda.engages(rows, d_k, d_v, c, KDA_SUB, dtype)


def _rule_segment(t, per, c, state, xs, first):
    """One segment of the rule: ``per`` chunks of ``c`` tokens from token
    ``first`` of the (padded) sequences, whose first ``t`` tokens are real.
    ``xs`` are the segment's ``q``, ``k``, ``v``, ``g`` and ``beta``, (N,
    per * c, ...).  Returns the state after it, its outputs and how many of
    its log-decays lay under the floor."""
    q, k, v, g, beta = xs
    n, _, h, _ = q.shape
    # padded tokens write nothing and decay nothing
    real = (first + jnp.arange(per * c)) < t
    g = jnp.where(real[:, None, None], g.astype(jnp.float32), 0.0)
    beta = jnp.where(real[:, None], beta.astype(jnp.float32), 0.0)
    # the floor: a token's decay of a channel is held to e^floor at least
    held = jnp.sum(g < KDA_DECAY_FLOOR, dtype=jnp.float32)
    g = jnp.maximum(g, KDA_DECAY_FLOOR)
    if _rule_in_kernel(per * c, q.shape[-1], v.shape[-1], c, q.dtype):
        state, out = pallas_kda.rule_segment(q, k, v, g, beta, state, c,
                                             KDA_SUB, KDA_DECAY_FLOOR)
        return state, out, held

    def chunked(x):
        # (chunks, N, H, C, ...): the inner scan runs over the first axis
        x = x.reshape((n, per, c) + x.shape[2:])
        return jnp.transpose(x, (1, 0, 3, 2) + tuple(range(4, x.ndim)))
    parts = _chunk_parts(*(chunked(x) for x in (q, k, v, g, beta)))
    state, out = _carry_state(state, tuple(parts))
    out = jnp.transpose(out, (1, 0, 3, 2, 4)).reshape(n, per * c, h, -1)
    return state, out, held


def _rows(x, first, count):
    return jax.lax.dynamic_slice_in_dim(x, first, count, axis=1)


def _cut(x, first, rows, lead):
    """The ``rows`` rows of ``x`` from row ``first`` behind the ``lead``
    rows before them, zeros where those lie before the sequence's start."""
    before = _rows(x, jnp.maximum(first - lead, 0), lead)
    return jnp.concatenate([jnp.where(first > 0, before, 0),
                            _rows(x, first, rows)], axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _segments(static, params, arrays):
    """``segment(params, state, xs, first)`` -> (state, outputs, a count)
    over the whole of ``arrays`` (each (N, T', ...), T' a multiple of the
    segment's ``rows``, ``rows`` no fewer than ``lead``, nothing padded in
    front), ``rows`` at a time:
    ``xs`` are the rows from ``first`` behind the ``lead`` rows before them
    (zeros before the first segment), the state (``state_shape``, float32)
    is carried from segment to segment from zero, and ``params`` are
    trained arrays; ``static`` is ``(segment, rows, lead, state_shape)``.
    Returns the outputs (N, T', ...) and the counts' sum.  A segment's rows
    are cut out of the arrays from row ``first``, where a tile starts, its
    ``lead`` rows by a slice of their own, and its outputs written from row
    ``first``.  The backward pass goes from the last segment to the first,
    making each again from the state it started with and writing its rows'
    cotangents from row ``first`` over the rows it has read: what is kept
    between the passes is a state a segment, what lives at once is what one
    segment makes, the arrays are held once, and nothing as long as the
    sequence is copied into another order."""
    return _segments_fwd(static, params, arrays)[0]


def _segments_fwd(static, params, arrays):
    segment, rows, lead, state_shape = static
    total = arrays[0].shape[1]
    if total % rows or rows < lead:
        raise ValueError('_segments: arrays of %d rows are no multiple of a '
                         'segment of %d rows, or it reads %d before it'
                         % (total, rows, lead))

    def cut(first):
        return tuple(_cut(x, first, rows, lead) for x in arrays)
    state = jnp.zeros(state_shape, jnp.float32)
    like = jax.eval_shape(lambda: segment(params, state, cut(0), 0)[1])
    out = jnp.zeros((like.shape[0], total) + like.shape[2:], like.dtype)

    def one(carry, first):
        state, out = carry
        after, rows_out, counted = segment(params, state, cut(first), first)
        out = jax.lax.dynamic_update_slice_in_dim(out, rows_out, first,
                                                  axis=1)
        return (after, out), (counted, state)

    (_, out), (counted, states) = jax.lax.scan(
        one, (state, out), jnp.arange(total // rows, dtype=jnp.int32) * rows)
    return (out, jnp.sum(counted)), (params, arrays, states)


def _segments_bwd(static, res, cotangent):
    params, arrays, states = res
    d_out, _ = cotangent
    segment, rows, lead, _ = static

    def one(carry, x):
        d_state, d_params, arrays, edge = carry
        first, state = x
        xs = tuple(_cut(a, first, rows, lead) for a in arrays)
        (_, _, counted), back = jax.vjp(
            lambda p, s, xs: segment(p, s, xs, first), params, state, xs)
        d_p, d_state, d_xs = back((d_state, _rows(d_out, first, rows),
                                   jnp.zeros_like(counted)))
        d_xs = tuple(d.astype(a.dtype) for d, a in zip(d_xs, arrays))
        # a segment's rows give way to their cotangents once it has read
        # them, so that the arrays are held once and not twice; its last
        # ``lead`` rows take what the segment after it found for them
        # (``edge``), and the cotangents of the ``lead`` rows before it, the
        # last rows of the segment before, which has yet to read them, wait
        # in ``edge`` in turn; the first segment's lie before the sequence
        arrays = tuple(
            jax.lax.dynamic_update_slice_in_dim(
                a, jnp.concatenate([d[:, lead:rows], d[:, rows:] + e],
                                   axis=1), first, axis=1)
            for a, d, e in zip(arrays, d_xs, edge))
        return (d_state, jax.tree_util.tree_map(jnp.add, d_params, d_p),
                arrays, tuple(d[:, :lead] for d in d_xs)), None

    firsts = jnp.arange(states.shape[0], dtype=jnp.int32) * rows
    (_, d_params, d_arrays, _), _ = jax.lax.scan(
        one, (jnp.zeros_like(states[0]),
              jax.tree_util.tree_map(jnp.zeros_like, params), arrays,
              tuple(jnp.zeros_like(a[:, :lead]) for a in arrays)),
        (firsts, states), reverse=True)
    return d_params, d_arrays


_segments.defvjp(_segments_fwd, _segments_bwd)


def _segmenting(chunk_size, t, sub, segment, lead):
    """How ``t`` tokens go: the tokens of a chunk (``chunk_size``, or the
    sequence if that is shorter, but no fewer than the ``lead`` rows a
    segment reads before it, up to a multiple of ``sub``), the chunks of a
    segment (up to ``segment``, a divisor of the chunks) and the tokens
    that pad the sequence to whole chunks."""
    c = -(-max(min(int(chunk_size), t), lead) // sub) * sub
    chunks = -(-t // c)
    per = next(s for s in range(min(segment, chunks), 0, -1)
               if chunks % s == 0)
    return c, per, chunks * c - t


def _padded(x, pad):
    return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))


def delta_rule_chunked(q, k, v, g, beta, chunk_size=64):
    """The gated delta rule with a decay for every channel, in chunks.
    ``q``, ``k`` (N, T, H, d_k), ``v`` (N, T, H, d_v), log-decay ``g``
    (N, T, H, d_k) and ``beta`` (N, T, H), the last two float32; ``q`` comes
    scaled.  Returns ``o`` (N, T, H, d_v) and how many of the log-decays lay
    under ``KDA_DECAY_FLOOR`` and were taken as the floor.  A sequence that
    is no multiple of the chunk is padded with tokens that write nothing
    (beta 0) and decay nothing.  The chunks go in segments of up to
    ``KDA_SEGMENT`` (``_segments``)."""
    n, t, h, d_k = q.shape
    c, per, pad = _segmenting(chunk_size, t, KDA_SUB, KDA_SEGMENT, 0)

    def segment(params, state, xs, first):
        return _rule_segment(t, per, c, state, xs, first)
    out, held = _segments(
        (segment, per * c, 0, (n, h, d_k, v.shape[-1])), (),
        tuple(_padded(x, pad) for x in (q, k, v, g, beta)))
    return out[:, :t], held


def _kda_prepare(heads, lead, kernels, a_log, dt_bias, xs):
    """A segment's queries, keys and values, float32 log-decay and beta, and
    its output gate by head, from its rows of the layer's projections behind
    the ``lead`` rows before it."""
    query, key, value, decay, beta, gate = xs

    def by_head(x):
        return x.reshape(x.shape[:2] + (heads, -1))
    with jax.named_scope('conv'):
        q, k, v = (by_head(causal_conv_silu(x, kernel, lead))
                   for x, kernel in zip((query, key, value), kernels))
        q = (_l2norm(q) * q.shape[-1] ** -0.5).astype(query.dtype)
        k = _l2norm(k).astype(query.dtype)
    with jax.named_scope('gates'):
        rate = -jnp.exp(a_log.astype(jnp.float32))[:, None]
        g = rate * jax.nn.softplus(
            by_head(decay[:, lead:]).astype(jnp.float32) +
            dt_bias.astype(jnp.float32).reshape(heads, -1))
        beta = jax.nn.sigmoid(beta[:, lead:].astype(jnp.float32))
    return (q, k, v, g, beta), by_head(gate[:, lead:])


def _kda_out_gate(eps, gamma, o, gate):
    """A segment's outputs normed over a head and gated."""
    with jax.named_scope('out_gate'):
        of = o.astype(jnp.float32)
        of = of * jax.lax.rsqrt(jnp.mean(of * of, axis=-1, keepdims=True) +
                                eps)
        out = of * gamma.astype(jnp.float32) * \
            jax.nn.sigmoid(gate.astype(jnp.float32))
        return out.reshape(out.shape[:2] + (-1,)).astype(o.dtype)


def _kimi_delta_attention_apply(attrs, inputs, is_train, rng):
    (query, key, value, q_kernel, k_kernel, v_kernel, decay, a_log, dt_bias,
     beta, gate, o_gamma, count_so_far) = inputs
    heads, eps = int(attrs['num_heads']), float(attrs['eps'])
    n, t, channels = query.shape
    lead = q_kernel.shape[1] - 1
    c, per, pad = _segmenting(attrs['chunk_size'], t, KDA_SUB, KDA_SEGMENT,
                              lead)

    def segment(params, state, xs, first):
        # the convolutions, the gates and the output's norm and gate run a
        # segment at a time with the rule, so that nothing they make is as
        # long as the sequence: their scopes lie under ``scan``
        kernels, a_log, dt_bias, gamma = params
        xs, gate = _kda_prepare(heads, lead, kernels, a_log, dt_bias, xs)
        state, out, held = _rule_segment(t, per, c, state, xs, first)
        return state, _kda_out_gate(eps, gamma, out, gate), held
    with jax.named_scope('scan'):
        out, held = _segments(
            (segment, per * c, lead, (n, heads) + (channels // heads,) * 2),
            ((q_kernel, k_kernel, v_kernel), a_log, dt_bias, o_gamma),
            tuple(_padded(x, pad)
                  for x in (query, key, value, decay, beta, gate)))
    step = jnp.stack([jnp.float32(n * t), jnp.float32(n * ((t + pad) // c)),
                      held])
    return [out[:, :t]], {'count': count_so_far.astype(jnp.float32) + step}


def _kimi_delta_attention_counters(now, before, attrs, in_shapes):
    """What the layer counted since the last drain, into the registry: its
    tokens, its chunks, the log-decays it computed (one a token and channel)
    and how many of them lay under ``KDA_DECAY_FLOOR`` and were held to it;
    and the chunks whose rule ran in the Pallas kernels: all of them or
    none, by the predicate that chose when the step was traced (a drain
    does not know the layer's dtype: float32 and bfloat16, what a module
    computes in, both pass)."""
    from .. import instrument
    count = now['count'] - (before['count'] if before else 0)
    _, t, channels = in_shapes[0]
    d = channels // int(attrs['num_heads'])
    c, per, _ = _segmenting(attrs['chunk_size'], t, KDA_SUB, KDA_SEGMENT,
                            int(attrs['kernel']) - 1)
    in_kernel = _rule_in_kernel(per * c, d, d, c, jnp.float32)
    instrument.inc('kda.tokens', int(count[0]))
    instrument.inc('kda.chunks', int(count[1]))
    instrument.inc('kda.chunks_in_kernel', int(count[1]) * in_kernel)
    instrument.inc('kda.decays', int(count[0]) * int(channels))
    instrument.inc('kda.decays_at_floor', int(count[2]))


def _kimi_delta_attention_complete(attrs, in_shapes):
    if in_shapes[0] is None:
        return in_shapes
    heads, taps = int(attrs['num_heads']), int(attrs['kernel'])
    n, t, channels = in_shapes[0]
    for i in (1, 2, 6, 10):
        _complete(in_shapes, i, (n, t, channels))
    for i in (3, 4, 5):
        _complete(in_shapes, i, (channels, taps))
    _complete(in_shapes, 7, (heads,))
    _complete(in_shapes, 8, (channels,))
    _complete(in_shapes, 9, (n, t, heads))
    _complete(in_shapes, 11, (channels // heads,))
    return in_shapes


register('KimiDeltaAttention', _kimi_delta_attention_apply,
         input_names=lambda attrs: [
             'query', 'key', 'value', 'q_conv_weight', 'k_conv_weight',
             'v_conv_weight', 'decay', 'A_log', 'dt_bias', 'beta', 'gate',
             'o_norm_gamma'],
         num_outputs=lambda attrs: 1,
         aux_names=lambda attrs: ['count'],
         aux_shape=lambda attrs, in_shapes: [(3,)],
         complete_shapes=_kimi_delta_attention_complete,
         keep_dtype=('A_log', 'dt_bias'),
         aux_counters=_kimi_delta_attention_counters,
         attr_defaults={'num_heads': None, 'kernel': 4, 'chunk_size': 64,
                        'eps': 1e-5},
         hint='kimideltaattention',
         doc='Kimi Delta Attention between its projections: query, key, '
             'value, decay and gate (N, T, H * d) and beta (N, T, H) -> '
             '(N, T, H * d).  A causal depthwise convolution and silu on '
             'query, key and value, an l2 norm on the first two; a log-decay '
             'for every channel, -exp(A_log) softplus(decay + dt_bias); the '
             'gated delta rule in chunks of chunk_size tokens with the state '
             'carried from chunk to chunk (on a TPU, at heads of a multiple '
             'of 128 channels and chunks of a multiple of 16 tokens, in two '
             'Pallas kernels, ops/pallas_kda.py; elsewhere by a scan in '
             'jnp); an RMS norm over a head and a sigmoid gate on the '
             'output.  Auxiliary state count (3,): running totals of '
             'tokens, chunks, and log-decays (one a token and channel) that '
             'lay under -10 and were held to it.')


# ---------------------------------------------------------------------------
# Mamba2Mixer: what lies between the two projections of a Mamba-2 layer
# (Dao and Gu 2024, arXiv:2405.21060; ``model_type`` ``nemotron_h``).  With H
# heads of P channels, G groups and a state of N a head, d = H P:
#
#   xBC <- silu(conv(xBC) + b_conv)     (causal, depthwise), [x | B | C] = xBC
#   dt  <- softplus(dt + dt_bias),  A = -exp(A_log), both one a head
#   S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T    (S is P x N; head h reads the
#   y_t = S_t C_t + D x_t                          B, C of group h // (H / G))
#   output RMSNorm_group(y * silu(z)) * gamma, the mean square over each
#   group's d / G channels
#
# with S_0 = 0 at every sequence's start.  As ``KimiDeltaAttention``, one
# scope, ``scan``, and under it ``conv``, ``gates`` and ``out_gate``: all of
# it runs a segment of chunks at a time inside ``_segments``, whose backward
# pass makes each segment again from the state it started with.  The
# recurrence runs in chunks of ``chunk_size`` tokens as matrix products (the
# state-space duality of the paper): with a_t = dt_t A and G_t its sum from
# the chunk's start, inside a chunk y_t = sum_{i <= t} (C_t . B_i) e^(G_t -
# G_i) dt_i x_i, a masked product of the chunk's C B^T (one a group) with the
# decays (one a head); the state a chunk leaves is e^(G_C) S_0 + sum_i e^(G_C
# - G_i) dt_i x_i B_i^T, the states at the chunks' starts follow from those
# by the decays between chunks, and a chunk adds e^(G_t) S_0 C_t to its
# outputs.  The decay is a scalar a head and every difference G_t - G_i is
# taken before its exponential and is at most 0: nothing is held to a floor
# or a clamp, and the chunked form is the recurrence up to rounding.  Sums
# and decays are float32; the products take the inputs' dtype and accumulate
# in float32.
# ---------------------------------------------------------------------------

SSM_SEGMENT = 8


def _decays(sums):
    """``e^(sums_t - sums_i)`` for ``i <= t`` along the last axis of the
    running sums ``sums``, 0 for ``i > t``: (..., t, i).  The difference is
    taken before the exponential and is at most 0."""
    size = sums.shape[-1]
    return jnp.exp(jnp.where(jnp.tril(jnp.ones((size, size), bool)),
                             sums[..., :, None] - sums[..., None, :],
                             -jnp.inf))


def ssd_chunked(x, b, c, dt, a, state, per):
    """The recurrence over ``per`` chunks from ``state`` (N, H, P, S),
    float32.  ``x`` (N, T, H, P), ``b`` and ``c`` (N, T, G, S), the step
    ``dt`` (N, T, H) float32 (0 for a token that writes and decays nothing)
    and the rate ``a`` (H,) float32, under 0.  Returns the state after the
    last chunk and ``y`` (N, T, H, P) without the ``D x`` term."""
    n, t, h, p = x.shape
    g, size, dtype = b.shape[2], t // per, x.dtype
    k = h // g                                  # heads a group

    def chunks(v, *by_group):
        return v.reshape((n, per, size) + (by_group or v.shape[2:]))
    total = jnp.cumsum(chunks(dt * a), axis=2)           # G: (N, Z, C, H)
    last = total[:, :, -1]                               # G_C: (N, Z, H)
    xs = chunks((x * dt[..., None]).astype(dtype), g, k, p)
    bs, cs = chunks(b), chunks(c)
    # inside a chunk: (C_t . B_i) e^(G_t - G_i) over i <= t, then dt_i x_i;
    # a head's (t, i) square is the last two axes
    pairs = jnp.einsum('nztgs,nzigs->nzgti', cs, bs,
                       preferred_element_type=jnp.float32)
    mixed = pairs[:, :, :, None] * _decays(total.transpose(0, 1, 3, 2)) \
        .reshape(n, per, g, k, size, size)
    inside = jnp.einsum('nzgkti,nzigkp->nztgkp', mixed.astype(dtype), xs,
                        preferred_element_type=jnp.float32)
    # what a chunk adds to the state it found: sum_i e^(G_C - G_i) dt_i x_i
    # B_i^T
    rest = jnp.exp(last[:, :, None] - total).reshape(n, per, size, g, k)
    added = jnp.einsum('nzigkp,nzigs->nzgkps',
                       (xs * rest[..., None]).astype(dtype), bs,
                       preferred_element_type=jnp.float32)
    # the states at the chunks' starts, and after the last: the state given
    # and what each chunk added, by the decays of the chunks between
    found = jnp.concatenate(
        [state[:, None], added.reshape(n, per, h, p, -1)], axis=1)
    sums = jnp.pad(jnp.cumsum(last, axis=1), ((0, 0), (1, 0), (0, 0)))
    starts = jnp.einsum('nhzj,njhps->nzhps',
                        _decays(sums.transpose(0, 2, 1)), found)
    before = jnp.einsum(
        'nztgs,nzgkps->nztgkp', cs,
        starts[:, :-1].astype(dtype).reshape(n, per, g, k, p, -1),
        preferred_element_type=jnp.float32) * \
        jnp.exp(total).reshape(n, per, size, g, k)[..., None]
    return starts[:, -1], (inside + before).reshape(n, t, h, p).astype(dtype)


def _ssm_segment(sizes, t, per, size, lead, params, state, xs, first):
    """One segment of the mixer: ``per`` chunks of ``size`` tokens from token
    ``first`` of the (padded) sequences, whose first ``t`` tokens are real;
    ``xs`` are its rows of ``z``, ``xBC`` and ``dt`` behind the ``lead`` rows
    before them.  Returns the state after it and its outputs."""
    heads, groups, states, eps = sizes
    kernel, bias, a_log, d, dt_bias, gamma = params
    z, xbc, dt = xs
    n, rows = z.shape[0], per * size
    channels = z.shape[-1]
    with jax.named_scope('conv'):
        xbc = causal_conv_silu(xbc, kernel, lead, bias)
        x = xbc[..., :channels].reshape(n, rows, heads, -1)
        b, c = (v.reshape(n, rows, groups, states) for v in jnp.split(
            xbc[..., channels:], 2, axis=-1))
    with jax.named_scope('gates'):
        # padded tokens write nothing and decay nothing
        real = (first + jnp.arange(rows)) < t
        dt = jnp.where(real[:, None], jax.nn.softplus(
            dt[:, lead:].astype(jnp.float32) + dt_bias.astype(jnp.float32)),
            0.0)
        a = -jnp.exp(a_log.astype(jnp.float32))
    state, y = ssd_chunked(x, b, c, dt, a, state, per)
    with jax.named_scope('out_gate'):
        y = y.astype(jnp.float32) + \
            d.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
        y = y.reshape(n, rows, groups, -1) * jax.nn.silu(
            z[:, lead:].astype(jnp.float32)).reshape(n, rows, groups, -1)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
        out = y.reshape(n, rows, channels) * gamma.astype(jnp.float32)
    return state, out.astype(z.dtype), jnp.float32(0)


def _mamba2_sizes(attrs):
    return (int(attrs['num_heads']), int(attrs['head_dim']),
            int(attrs['state_size']), int(attrs['num_groups']))


def _mamba2_mixer_apply(attrs, inputs, is_train, rng):
    (z, xbc, dt, kernel, bias, a_log, d, dt_bias, gamma,
     count_so_far) = inputs
    heads, _, states, groups = _mamba2_sizes(attrs)
    n, t, _ = z.shape
    lead = kernel.shape[1] - 1
    size, per, pad = _segmenting(attrs['chunk_size'], t, 1, SSM_SEGMENT,
                                  lead)
    segment = functools.partial(
        _ssm_segment, (heads, groups, states, float(attrs['eps'])), t, per,
        size, lead)
    with jax.named_scope('scan'):
        out, _ = _segments(
            (segment, per * size, lead,
             (n, heads, z.shape[-1] // heads, states)),
            (kernel, bias, a_log, d, dt_bias, gamma),
            tuple(_padded(v, pad) for v in (z, xbc, dt)))
    step = jnp.asarray([n * t, n * ((t + pad) // size)], jnp.float32)
    return [out[:, :t]], {'count': count_so_far.astype(jnp.float32) + step}


def _mamba2_mixer_counters(now, before, attrs, in_shapes):
    """The layer's tokens and chunks since the last drain, into the
    registry.  The chunked form holds nothing to a floor, so there is no
    third count as ``kda.decays_at_floor``."""
    from .. import instrument
    count = now['count'] - (before['count'] if before else 0)
    instrument.inc('ssm.tokens', int(count[0]))
    instrument.inc('ssm.chunks', int(count[1]))


def _mamba2_mixer_complete(attrs, in_shapes):
    if in_shapes[0] is None:
        return in_shapes
    heads, size, states, groups = _mamba2_sizes(attrs)
    n, t, channels = in_shapes[0]
    if channels != heads * size:
        raise ValueError('Mamba2Mixer: z has %d channels, not num_heads x '
                         'head_dim = %d' % (channels, heads * size))
    mixed = channels + 2 * groups * states
    _complete(in_shapes, 1, (n, t, mixed))
    _complete(in_shapes, 2, (n, t, heads))
    _complete(in_shapes, 3, (mixed, int(attrs['kernel'])))
    _complete(in_shapes, 4, (mixed,))
    for i in (5, 6, 7):
        _complete(in_shapes, i, (heads,))
    _complete(in_shapes, 8, (channels,))
    return in_shapes


register('Mamba2Mixer', _mamba2_mixer_apply,
         input_names=lambda attrs: [
             'z', 'xBC', 'dt', 'conv_weight', 'conv_bias', 'A_log', 'D',
             'dt_bias', 'norm_gamma'],
         num_outputs=lambda attrs: 1,
         aux_names=lambda attrs: ['count'],
         aux_shape=lambda attrs, in_shapes: [(2,)],
         complete_shapes=_mamba2_mixer_complete,
         keep_dtype=('A_log', 'D', 'dt_bias'),
         aux_counters=_mamba2_mixer_counters,
         attr_defaults={'num_heads': None, 'head_dim': None,
                        'state_size': None, 'num_groups': 1, 'kernel': 4,
                        'chunk_size': 128, 'eps': 1e-5},
         hint='mamba2mixer',
         doc='A Mamba-2 layer between its projections: z (N, T, H * P), xBC '
             '(N, T, H * P + 2 G S) and dt (N, T, H) -> (N, T, H * P), for '
             'num_heads H of head_dim P, num_groups G and state_size S.  A '
             'causal depthwise convolution with a bias and silu on xBC = [x '
             '| B | C]; a step softplus(dt + dt_bias) and a rate -exp(A_log) '
             'a head; the state S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_t^T '
             'and y_t = S_t C_t + D x_t, head h with the B and C of group h '
             '// (H / G), in chunks of chunk_size tokens as masked matrix '
             'products with the state carried from chunk to chunk; an RMS '
             'norm of y * silu(z) over each group\'s channels, times '
             'norm_gamma.  Auxiliary state count (2,): running totals of '
             'tokens and chunks.')
