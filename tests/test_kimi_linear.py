"""Kimi Linear on the CPU at a small size against its plain reference
(``mxnet_tpu/models/kimi_linear_reference.py``): the chunked delta rule
against the recurrence token by token, forward and gradients, in the jnp
form and in the Pallas kernels of ``ops/pallas_kda.py`` through the
interpreter (heads of 128 channels, the width their predicate accepts); values
narrower than keys through ``gqa_attention``'s two paths; the whole model's
log-probabilities, loss and every parameter's gradient, in float32 and
bf16; one ``Module.fit`` step with Adam; the wrong models the benchmark's
``correct`` has to refuse; the shares of the expert layer, with the shared
expert counted once, adding up to the uncut layer; and what the step had to
learn for it (a mirror stage round a scan, a scope named by the symbol,
arrays that keep their dtype, counters computed on the device).

Sizes: hidden 48, layers ``kda, kda, kda, mla, kda`` (the first dense), 4
heads of 8 in Kimi Delta Attention with chunks of 16, 4 heads of 8 + 4 and 8
over a latent of 16, 16 experts of which 4 a token and one shared,
vocabulary 512, 2 x 40 = 80 tokens (no multiple of the chunk).
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import instrument, models
from mxnet_tpu.executor import _mirror_stage_units
from mxnet_tpu.models import kimi_linear_reference as ref
from mxnet_tpu.ops import lm, pallas_attention, pallas_kda
from mxnet_tpu.ops.registry import get_op
from mxnet_tpu.parallel.train_step import make_fit_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, T, HIDDEN, VOCAB = 2, 40, 48, 512
LINEAR = {'full_attn_layers': [4], 'kda_layers': [1, 2, 3, 5], 'head_dim': 8,
          'num_heads': 4, 'short_conv_kernel_size': 4}
SIZES = dict(vocab_size=VOCAB, hidden_size=HIDDEN, num_hidden_layers=5,
             first_k_dense_replace=1, intermediate_size=96,
             moe_intermediate_size=24, num_experts=16,
             num_experts_per_token=4, num_shared_experts=1,
             experts_held=(0, 16), num_attention_heads=4, kv_lora_rank=16,
             qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
             linear_attn_config=LINEAR, kda_gate_rank=8, kda_chunk_size=16,
             routed_scaling_factor=2.446, rms_norm_eps=1e-5)
SHAPES = {'data': (N, T), 'softmax_label': (N, T)}


def rel(got, want):
    got = np.asarray(jnp.asarray(got, jnp.float32), np.float64)
    want = np.asarray(jnp.asarray(want, jnp.float32), np.float64)
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def draw(rng, shape, scale=1.0):
    return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)


def make_params(symbol, seed, shapes=None):
    """Seeded arguments and auxiliary states of a model symbol."""
    rng = np.random.default_rng(seed)
    shapes = shapes or SHAPES
    arg_shapes, _, aux_shapes = symbol.infer_shape(**shapes)
    args, aux = {}, {}
    for name, shape in zip(symbol.list_arguments(), arg_shapes):
        if name in shapes:
            continue
        if name.endswith('_gamma'):
            args[name] = 1.0 + draw(rng, shape, 0.1)
        elif name.endswith('_A_log'):
            args[name] = jnp.asarray(np.log(rng.uniform(1, 16, shape)),
                                     jnp.float32)
        elif name.endswith('_dt_bias'):
            # steps of 0.001 to 0.03, as a published model's start: with
            # rates to 16 no log-decay here reaches the operator's floor
            args[name] = jnp.asarray(rng.uniform(-7, -3.5, shape),
                                     jnp.float32)
        else:
            args[name] = draw(rng, shape, 1.0 / np.sqrt(shape[1]))
    for name, shape in zip(symbol.list_auxiliary_states(), aux_shapes):
        aux[name] = draw(rng, shape, 0.1) if name.endswith('_expert_bias') \
            else jnp.zeros(shape, jnp.float32)
    return args, aux


def with_bias(args, aux):
    out = dict(args)
    out.update({k: v for k, v in aux.items() if k.endswith('_expert_bias')})
    return out


def reference_config(**changes):
    config = {k: SIZES[k] for k in (
        'first_k_dense_replace', 'num_attention_heads', 'kv_lora_rank',
        'qk_nope_head_dim', 'qk_rope_head_dim', 'v_head_dim', 'num_experts',
        'num_experts_per_token', 'experts_held', 'rms_norm_eps',
        'routed_scaling_factor')}
    config.update(layer_types=['kda', 'kda', 'kda', 'mla', 'kda'],
                  kda_num_heads=LINEAR['num_heads'], moe_renormalize=True)
    config.update(changes)
    return config


class GradsOut(object):
    """A stand-in optimizer that hands the step's gradients back."""

    def update(self, params, grads, state, lr_t):
        return params, grads


def run_step(symbol, args, aux, tokens, labels, dtype):
    step = make_fit_step(symbol, GradsOut(), data_names=('data',),
                         compute_dtype=None if dtype == jnp.float32
                         else dtype, donate=False)
    batch = {'data': jnp.asarray(tokens, jnp.float32),
             'softmax_label': jnp.asarray(labels, jnp.float32)}
    outs, _, new_aux, grads = step(dict(args), {}, dict(aux), {}, batch,
                                   jnp.float32(0), jax.random.PRNGKey(0))
    return np.asarray(outs[0].astype(jnp.float32), np.float64), new_aux, grads


# -- the chunked delta rule against the recurrence --------------------------

def rule_inputs(seed, t, strength, n=2, h=3, d_k=8, d_v=6):
    rng = np.random.default_rng(seed)
    q = draw(rng, (n, t, h, d_k))
    k = draw(rng, (n, t, h, d_k))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = draw(rng, (n, t, h, d_v))
    g = -strength * jnp.asarray(rng.uniform(0, 1, (n, t, h, d_k)),
                                jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, (n, t, h)), jnp.float32)
    return q, k, v, g, beta


# length, chunk, the decay's strength a token (log-decays drawn evenly from
# minus that to 0); and whether a token's log-decay passes the floor the
# chunked form holds it to (``lm.KDA_DECAY_FLOOR``, -10), which the rule
# counts
RULE_CASES = [
    (64, 8, 0.1, False),        # whole chunks, one sub-block each
    (128, 64, 1.0, False),      # eight sub-blocks a chunk
    (70, 32, 0.1, False),       # no multiple of the chunk
    (40, 64, 0.5, False),       # shorter than one chunk
    (96, 64, 9.9, False),       # sums of -79 inside a sub-block: no floor yet
    (96, 64, 16.0, True),       # decays strong enough to reach the floor
    (300, 128, 14.0, True),     # sixteen sub-blocks, two segments, padding
]


# the rule's two forms: ``reference`` is the jnp form (what a CPU runs), and
# ``interpret`` the Pallas kernels of ``ops/pallas_kda.py`` through the
# interpreter, at heads of 128 channels, the width their predicate accepts
PATHS = ['reference', 'interpret']


def take_path(monkeypatch, path):
    monkeypatch.delenv('MXTPU_DISABLE_PALLAS', raising=False)
    monkeypatch.delenv('MXTPU_ASSUME_TPU', raising=False)
    if path == 'interpret':
        monkeypatch.setenv('MXTPU_FORCE_PALLAS_INTERPRET', '1')
    else:
        monkeypatch.delenv('MXTPU_FORCE_PALLAS_INTERPRET', raising=False)


def kernel_calls(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count('pallas_call')


@pytest.mark.parametrize('path', PATHS)
@pytest.mark.parametrize('t, chunk, strength, floor', RULE_CASES)
def test_chunked_delta_rule_is_the_recurrence_forward_and_backward(
        t, chunk, strength, floor, path, monkeypatch):
    monkeypatch.setattr(lm, 'KDA_SEGMENT', 2)
    take_path(monkeypatch, path)
    wide = dict(n=1, h=2, d_k=128, d_v=128) if path == 'interpret' else {}
    inputs = rule_inputs(t, t, strength, **wide)
    cot = draw(np.random.default_rng(1), inputs[2].shape)

    def held(q, k, v, g, beta):
        """The recurrence with each log-decay held to the floor: what the
        chunked form computes, to rounding."""
        return ref.delta_rule(q, k, v, jnp.maximum(g, lm.KDA_DECAY_FLOOR),
                              beta)

    def chunked(*a):
        return lm.delta_rule_chunked(*a, chunk_size=chunk)

    def gradients(rule):
        return jax.grad(lambda *a: jnp.sum(rule(*a) * cot),
                        argnums=(0, 1, 2, 3, 4))(*inputs)
    # the kernels take every chunk that is a multiple of 16 tokens (a bf16
    # tile's rows), whole segments, padded tails and floors alike: the
    # segment's forward kernel; differentiated, that kernel in the forward
    # pass, again with every chunk's entering state, and the backward kernel
    c = lm._segmenting(chunk, t, lm.KDA_SUB, lm.KDA_SEGMENT, 0)[0]
    in_kernel = path == 'interpret' and c % 16 == 0
    assert kernel_calls(chunked, *inputs) == (1 if in_kernel else 0)
    assert kernel_calls(lambda *a: gradients(lambda *b: chunked(*b)[0]),
                        *inputs) == (3 if in_kernel else 0)
    with jax.default_matmul_precision('highest'):
        want = held(*inputs)
        got, at_floor = chunked(*inputs)
        assert rel(got, want) < 1e-5
        # every log-decay under the floor is counted, and no other
        assert int(at_floor) == int((inputs[3] < lm.KDA_DECAY_FLOOR).sum())
        assert bool(at_floor > 0) == floor
        # against the recurrence as the reference states it, with no floor:
        # the same where no decay reached it, and where one did a decay of
        # e^-10 for one of e^-16 at most: a ten-thousandth of the output
        assert rel(got, ref.delta_rule(*inputs)) < (1e-4 if floor else 1e-5)
        want_grads = gradients(held)
        got_grads = gradients(lambda *a: chunked(*a)[0])
        if in_kernel:
            # and against the second oracle, the jnp form
            take_path(monkeypatch, 'reference')
            assert kernel_calls(chunked, *inputs) == 0
            jnp_form = chunked(*inputs)[0]
            jnp_grads = gradients(lambda *a: chunked(*a)[0])
            assert rel(got, jnp_form) < 1e-5
            for name, a, b in zip('q k v g beta'.split(), got_grads,
                                  jnp_grads):
                assert rel(a, b) < 5e-5, name
    for name, a, b in zip('q k v g beta'.split(), got_grads, want_grads):
        assert np.isfinite(np.asarray(a)).all(), name
        assert rel(a, b) < 5e-5, name


def segments_inputs(n, t, h, d, seed=5):
    """``KimiDeltaAttention``'s inputs, seeded, with the drawn cotangent of
    its output."""
    rng = np.random.default_rng(seed)
    wide = lambda *shape: draw(rng, shape)
    inputs = [wide(n, t, h * d), wide(n, t, h * d), wide(n, t, h * d),
              wide(h * d, 4), wide(h * d, 4), wide(h * d, 4),
              4.0 * wide(n, t, h * d),
              jnp.log(jnp.asarray([1.0, 4.0, 16.0][:h])),
              wide(h * d), wide(n, t, h), wide(n, t, h * d),
              1.0 + 0.1 * wide(d), jnp.zeros((3,))]
    return inputs, wide(n, t, h * d)


# chunks a segment -> tokens a sequence in chunks of 16: 90 in 6 chunks (6
# tokens of padding) in six segments or three; 190 in 12 chunks (2 of
# padding, fewer than the three rows before a segment) in three of four
SEGMENT_LENGTHS = {1: 90, 2: 90, 4: 190}


@pytest.mark.parametrize('path', PATHS)
@pytest.mark.parametrize('per', sorted(SEGMENT_LENGTHS))
def test_the_layer_in_segments_is_the_layer_in_one(per, path, monkeypatch):
    """The operator's convolutions read three rows before a segment, the
    last rows of the segment before it (zeros before the first segment);
    the backward pass writes a segment's cotangents from its first row over
    the rows it has read and keeps those three rows' until the segment
    before has read them, and the first segment's are dropped: output,
    count and all twelve gradients are what one segment of all the chunks
    gives, the padded rows of the last segment too.  In the kernels as in
    the jnp form, and the two agree."""
    take_path(monkeypatch, path)
    t = SEGMENT_LENGTHS[per]
    n, h, d = (1, 2, 128) if path == 'interpret' else (2, 3, 8)
    inputs, cot = segments_inputs(n, t, h, d)
    attrs = {'num_heads': h, 'kernel': 4, 'chunk_size': 16, 'eps': 1e-5}

    def loss(*xs):
        outs, aux = get_op('KimiDeltaAttention').apply(attrs, list(xs), True,
                                                       None)
        return jnp.sum(outs[0] * cot), (outs[0], aux['count'])

    def run():
        with jax.default_matmul_precision('highest'):
            return jax.value_and_grad(loss, argnums=tuple(range(12)),
                                      has_aux=True)(*inputs)
    (_, (want, want_count)), want_grads = run()      # every chunk, one segment
    monkeypatch.setattr(lm, 'KDA_SEGMENT', per)
    assert kernel_calls(lambda *xs: loss(*xs)[0], *inputs) == \
        (1 if path == 'interpret' else 0)
    (_, (got, got_count)), got_grads = run()
    assert rel(got, want) < 1e-6
    np.testing.assert_array_equal(np.asarray(got_count),
                                  np.asarray(want_count))
    # the tokens a sequence in chunks of 16, and some decays under the floor
    assert want_count[0] == n * t and want_count[1] == n * -(-t // 16)
    assert 0 < want_count[2] < n * t * h * d / 2
    for i, (a, b) in enumerate(zip(got_grads, want_grads)):
        assert np.isfinite(np.asarray(a)).all(), i
        assert rel(a, b) < 2e-5, i
    if path == 'interpret':
        take_path(monkeypatch, 'reference')
        (_, (jnp_form, _)), jnp_grads = run()
        assert rel(got, jnp_form) < 1e-5
        for i, (a, b) in enumerate(zip(got_grads, jnp_grads)):
            assert rel(a, b) < 5e-5, i


def front_pads(fn, args, length):
    """The pads in the program of ``fn`` at ``args``, forward and through
    ``jax.vjp``, that put rows in front of axis 1 of an array of ``length``
    rows or more: (rows in front, rows), one a pad, sub-programs included."""
    from jax.extend import core

    def both(*xs):
        out, back = jax.vjp(fn, *xs)
        return out, back(jnp.ones_like(out))

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == 'pad':
                shape = eqn.invars[0].aval.shape
                low = eqn.params['padding_config'][1][0]
                if len(shape) > 1 and shape[1] >= length and low:
                    yield low, shape[1]
            for value in eqn.params.values():
                for inner in (value if isinstance(value, (tuple, list))
                              else [value]):
                    if isinstance(inner, core.ClosedJaxpr):
                        inner = inner.jaxpr
                    if isinstance(inner, core.Jaxpr):
                        yield from walk(inner)
    return list(walk(jax.make_jaxpr(both)(*args).jaxpr))


@pytest.mark.parametrize('t', [90, 96])
def test_the_layer_pads_nothing_in_front_of_its_arrays(t, monkeypatch):
    """``_segments`` cuts a segment's three rows before it apart from its
    rows, so no array as long as the sequence is padded in front, forward
    or backward (the convolutions' own pads are a segment long); and it
    refuses arrays whose length is no multiple of a segment."""
    monkeypatch.setattr(lm, 'KDA_SEGMENT', 2)          # segments of 32 rows
    inputs, _ = segments_inputs(2, t, 2, 8)
    attrs = {'num_heads': 2, 'kernel': 4, 'chunk_size': 16, 'eps': 1e-5}

    def layer(*xs):
        return get_op('KimiDeltaAttention').apply(
            attrs, list(xs) + inputs[12:], True, None)[0][0]
    assert front_pads(layer, inputs[:12], t) == []
    # the rule without the convolutions reads no row before a segment
    q, k, v, g, beta = rule_inputs(1, t, 0.5, d_k=8, d_v=8)
    assert front_pads(lambda *a: lm.delta_rule_chunked(*a, chunk_size=16)[0],
                      (q, k, v, g, beta), t) == []

    def segment(params, state, xs, first):
        return state, xs[0][:, 3:], jnp.float32(0)
    with pytest.raises(ValueError, match='no multiple'):
        lm._segments((segment, 32, 3, (1,)), (), (inputs[0][:, :t - 8],))


def test_chunked_delta_rule_in_bf16_follows_the_recurrence():
    q, k, v, g, beta = rule_inputs(3, 128, 1.0, d_k=16, d_v=16)
    low = [x.astype(jnp.bfloat16) for x in (q, k, v)]
    want = ref.delta_rule(*[x.astype(jnp.float32) for x in low], g, beta)
    got, _ = lm.delta_rule_chunked(*low, g, beta, chunk_size=64)
    assert got.dtype == jnp.bfloat16
    assert rel(got, want) < 4e-2


def test_the_kernels_in_bf16_are_no_farther_from_the_recurrence_than_jnp(
        monkeypatch):
    """Output and all five gradients in bf16: the kernels round where the
    jnp form on a TPU rounds (on this CPU the jnp form's triangular algebra
    is float32, more than a TPU gives it), and stay as near the float32
    recurrence."""
    q, k, v, g, beta = rule_inputs(3, 128, 1.0, n=1, h=2, d_k=128, d_v=128)
    low = [x.astype(jnp.bfloat16) for x in (q, k, v)]
    exact = [x.astype(jnp.float32) for x in low] + [g, beta]
    cot = draw(np.random.default_rng(1), v.shape)

    def both(rule, inputs):
        def loss(*a):
            out = rule(*a)
            return jnp.sum(out.astype(jnp.float32) * cot), out
        grads, out = jax.grad(loss, argnums=(0, 1, 2, 3, 4),
                              has_aux=True)(*inputs)
        return (out,) + grads
    want = both(ref.delta_rule, exact)
    chunked = lambda *a: lm.delta_rule_chunked(*a, chunk_size=64)[0]
    take_path(monkeypatch, 'reference')
    jnp_form = both(chunked, low + [g, beta])
    take_path(monkeypatch, 'interpret')
    assert kernel_calls(chunked, *low, g, beta) == 1
    kernels = both(chunked, low + [g, beta])
    assert kernels[0].dtype == jnp.bfloat16
    for name, got, other, true in zip('out q k v g beta'.split(), kernels,
                                      jnp_form, want):
        assert np.isfinite(np.asarray(got, np.float32)).all(), name
        assert rel(got, true) < 4e-2, name
        assert rel(got, true) < 1.1 * rel(other, true), name


@pytest.mark.parametrize('d, chunk, in_kernel', [
    (128, 16, True),        # what the kernels were written for
    (8, 16, False),         # a head narrower than a lane tile
    (128, 8, False),        # a chunk shorter than a bf16 tile's rows
])
def test_the_counter_says_which_form_ran(d, chunk, in_kernel, monkeypatch):
    """The choice is a static predicate on shapes: the layer outside it
    takes the jnp form under the interpreter too and counts no chunk in
    ``kda.chunks_in_kernel``; inside it every chunk."""
    take_path(monkeypatch, 'interpret')
    n, t, h = 1, 40, 2
    rng = np.random.default_rng(6)
    wide = lambda *shape: draw(rng, shape)
    inputs = [wide(n, t, h * d), wide(n, t, h * d), wide(n, t, h * d),
              wide(h * d, 4), wide(h * d, 4), wide(h * d, 4),
              wide(n, t, h * d), jnp.zeros((h,)), wide(h * d), wide(n, t, h),
              wide(n, t, h * d), 1.0 + 0.1 * wide(d), jnp.zeros((3,))]
    attrs = {'num_heads': h, 'kernel': 4, 'chunk_size': chunk, 'eps': 1e-5}
    apply = lambda *xs: get_op('KimiDeltaAttention').apply(
        attrs, list(xs), True, None)
    assert kernel_calls(apply, *inputs) == (1 if in_kernel else 0)
    _, aux = apply(*inputs)
    chunks = n * -(-t // chunk)
    assert aux['count'].shape == (3,) and aux['count'][1] == chunks
    was = instrument.metrics_enabled()
    instrument.set_metrics(True)
    try:
        before = instrument.metrics_snapshot()['counters']
        lm._kimi_delta_attention_counters(
            {'count': np.asarray(aux['count'])}, None, attrs,
            [x.shape for x in inputs[:-1]])
        after = instrument.metrics_snapshot()['counters']
    finally:
        instrument.set_metrics(was)
    moved = {k: after.get(k, 0) - before.get(k, 0)
             for k in ('kda.chunks', 'kda.chunks_in_kernel')}
    assert moved == {'kda.chunks': chunks,
                     'kda.chunks_in_kernel': chunks if in_kernel else 0}
    # off the interpreter, on this CPU, the jnp form runs whatever the shape
    take_path(monkeypatch, 'reference')
    assert kernel_calls(lambda *xs: apply(*xs), *inputs) == 0
    assert not lm._rule_in_kernel(48, d, d, chunk, jnp.float32)
    assert pallas_kda.admits(48, d, d, chunk, lm.KDA_SUB,
                             jnp.bfloat16) == in_kernel
    assert not pallas_kda.admits(48, d, d, chunk, lm.KDA_SUB, jnp.float16)


def test_the_triangular_inverse_is_exact_in_two_steps():
    rng = np.random.default_rng(0)
    for c, block in ((8, 8), (64, 8), (128, 8), (24, 24)):
        lower = jnp.tril(draw(rng, (3, c, c), 0.5), -1)
        with jax.default_matmul_precision('highest'):
            got = lm._unit_lower_inverse(lower, block)
        want = np.linalg.inv(np.eye(c) + np.asarray(lower, np.float64))
        assert np.abs(np.asarray(got) - want).max() < 1e-4 * \
            np.abs(want).max()


# -- values narrower than keys ----------------------------------------------

def attention_inputs(t=128, heads=4, kv=2, d=24, d_v=16):
    rng = np.random.default_rng(2)
    return (draw(rng, (2, heads, t, d)), draw(rng, (2, kv, t, d)),
            draw(rng, (2, kv, t, d_v)))


def plain_attention(q, k, v, scale):
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    scores = jnp.einsum('bhtd,bhsd->bhts', q, k) * scale
    mask = jnp.tril(jnp.ones(scores.shape[-2:], bool))
    return jnp.einsum('bhts,bhsd->bhtd', jax.nn.softmax(
        jnp.where(mask, scores, -jnp.inf), axis=-1), v)


def splash_interpreted(monkeypatch):
    """``gqa_attention``'s kernel path on the CPU: the splash kernel in
    Pallas' interpret mode."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel)
    make = kernel.make_splash_mqa_single_device
    monkeypatch.setattr(
        kernel, 'make_splash_mqa_single_device',
        lambda **kwargs: make(interpret=True, **kwargs))
    monkeypatch.setattr(pallas_attention, '_mode',
                        lambda seq_len=None: 'kernel')
    pallas_attention._splash_kernel.cache_clear()


@pytest.mark.parametrize('path', ['jnp', 'kernel'])
def test_values_narrower_than_keys_through_gqa_attention(path, monkeypatch):
    q, k, v = attention_inputs()
    scale = 0.37
    if path == 'kernel':
        splash_interpreted(monkeypatch)
    cot = draw(np.random.default_rng(4), (2, 4, 128, 16))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * cot)
    try:
        with jax.default_matmul_precision('highest'):
            got = pallas_attention.gqa_attention(q, k, v, causal=True,
                                                 scale=scale)
            want = plain_attention(q, k, v, scale)
            assert got.shape == (2, 4, 128, 16)
            assert rel(got, want) < 1e-5
            got_grads = jax.grad(loss(lambda *a: pallas_attention.gqa_attention(
                *a, causal=True, scale=scale)), argnums=(0, 1, 2))(q, k, v)
            want_grads = jax.grad(loss(lambda *a: plain_attention(
                *a, scale)), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(got_grads, want_grads):
            assert rel(a, b) < 1e-4
    finally:
        pallas_attention._splash_kernel.cache_clear()


def test_gqa_attention_refuses_shapes_that_do_not_go_together():
    q, k, v = attention_inputs()
    with pytest.raises(ValueError):
        pallas_attention.gqa_attention(q, k[..., :8], v)
    with pytest.raises(ValueError):
        pallas_attention.gqa_attention(q, k, v[:, :1])


def test_flash_attention_symbol_infers_the_values_size():
    q, k, v = (mx.sym.Variable(n) for n in 'qkv')
    out = mx.sym.FlashAttention(q, k, v, causal=True, scale=0.1)
    _, shapes, _ = out.infer_shape(q=(2, 4, 32, 12), k=(2, 4, 32, 12),
                                   v=(2, 4, 32, 8))
    assert shapes == [(2, 4, 32, 8)]


# -- the whole model ---------------------------------------------------------

@pytest.fixture(scope='module')
def model():
    symbol = models.get_symbol('kimi_linear', seq_len=T, **SIZES)
    args, aux = make_params(symbol, 3)
    rng = np.random.default_rng(5)
    tokens = rng.integers(0, VOCAB, (N, T))
    labels = rng.integers(0, VOCAB, (N, T))
    everything = with_bias(args, aux)
    log_prob, load = ref.forward(everything, tokens, reference_config())
    loss, grads = ref.loss_and_grads(everything, tokens, labels,
                                     reference_config())
    return dict(symbol=symbol, args=args, aux=aux, tokens=tokens,
                labels=labels, log_prob=np.asarray(log_prob, np.float64),
                load=load, loss=float(loss), grads=grads)


def test_model_symbol_round_trips_and_lists_every_reference_array(model):
    symbol = model['symbol']
    text = symbol.tojson()
    assert mx.sym.load_json(text).tojson() == text
    assert 'kimi_linear' in models.list_models()
    names = set(symbol.list_arguments()) - set(SHAPES)
    names |= {n for n in symbol.list_auxiliary_states()
              if n.endswith('_expert_bias')}
    assert names == set(ref.param_names(reference_config()))
    # embedding and head are two tables
    assert {'embed_weight', 'lm_head_weight'} <= names


def test_model_float32_agrees_tightly(model):
    prob, aux, grads = run_step(model['symbol'], model['args'], model['aux'],
                                model['tokens'], model['labels'], jnp.float32)
    assert np.abs(np.log(prob) - model['log_prob']).max() < 2e-4
    picked = np.log(prob)[np.arange(N * T), model['labels'].reshape(-1)]
    assert abs(-picked.sum() - model['loss']) < 1e-3 * model['loss']
    assert set(grads) == set(model['grads'])
    for name, want in model['grads'].items():
        assert rel(grads[name], want) < 2e-4, name
    for layer, load in model['load'].items():
        np.testing.assert_array_equal(
            np.asarray(aux['l%d_moe_expert_load' % layer]), np.asarray(load))
        np.testing.assert_array_equal(
            np.asarray(aux['l%d_moe_expert_count' % layer]),
            [N * T * 4, N * T * 4, 0, 0])
    for layer in (0, 1, 2, 4):
        tokens, chunks, _ = np.asarray(aux['l%d_kda_count' % layer])
        assert (tokens, chunks) == (N * T, N * 3)


# bf16 at this file's widths (heads of 8, hidden 48, 4 of 16 experts) is
# several times as far from the float32 reference as at the published
# widths: four delta-rule layers each leave 1% of rounding in their output
# and a routing of 4 among 16 near-equal scores tips for a tenth of the
# tokens.  On the chip at the cell's size the program reads
# ``token_error_median`` 0.024 and ``gradient_error_median`` 0.027 (PERF.md
# section 6, PR 34), inside the driver's limits of 0.1 and 0.12; here it
# reads 0.14 and 0.7, so what is held here is its distance from every wrong
# model below (0.23 to 1.19), each of which the driver's limit refuses, and
# the limits themselves are held on the chip.
SMALL_BF16_TOKEN_ERROR_MAX = 0.19


def test_model_bf16_stays_nearer_the_reference_than_any_wrong_model(model):
    from benchmark.drivers import fit_kimi_linear as driver
    prob, aux, grads = run_step(model['symbol'], model['args'], model['aux'],
                                model['tokens'], model['labels'],
                                jnp.bfloat16)
    readings = driver.forward_readings(prob, np.exp(model['log_prob']))
    print('bf16 program', readings)
    assert readings['token_error_median'] < SMALL_BF16_TOKEN_ERROR_MAX
    assert readings['row_agreement'] > 0.9
    picked = np.log(prob)[np.arange(N * T), model['labels'].reshape(-1)]
    assert abs(-picked.sum() - model['loss']) < 0.02 * model['loss']
    for name, want in model['grads'].items():
        assert grads[name].dtype == jnp.float32
        assert np.isfinite(np.asarray(grads[name])).all(), name
    for layer in model['load']:
        assert float(aux['l%d_moe_expert_count' % layer][2]) == 0


# -- what the benchmark's ``correct`` refuses --------------------------------
# Each wrong model is the plain reference with one thing changed, against
# the bf16 program, through ``benchmark/drivers/fit_kimi_linear.py``'s own
# measures and limits.

def rotary(x, theta=10000.0):
    t, d = x.shape[-2:]
    half = d // 2
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * \
        theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)[None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(angle) - x2 * jnp.sin(angle),
                            x2 * jnp.cos(angle) + x1 * jnp.sin(angle)], -1)


def decay_a_head(z, p, heads, plain=ref.kda_gates):
    g, beta = plain(z, p, heads)
    return jnp.broadcast_to(g.mean(axis=-1, keepdims=True), g.shape), beta


def no_beta(z, p, heads, plain=ref.kda_gates):
    g, beta = plain(z, p, heads)
    return g, jnp.ones_like(beta)


def no_outer_product(q, k, v, g, beta):
    """The delta rule without its ``k k^T`` term: S_t = Diag(alpha) S_{t-1}
    + beta k v^T, gated linear attention."""
    def token(state, x):
        q, k, v, g, beta = x
        state = state * jnp.exp(g)[..., None] + jnp.einsum(
            'nhk,nhv->nhkv', k, beta[..., None] * v)
        return state, jnp.einsum('nhk,nhkv->nhv', q, state)
    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    n, _, h, d = q.shape
    out = jax.lax.scan(token, jnp.zeros((n, h, d, v.shape[-1])), xs)[1]
    return jnp.moveaxis(out, 0, 1)


def rotary_attention(q, k, v, scale, plain=ref.causal_attention):
    return plain(rotary(q), rotary(k), v, scale)


def scaled(params, suffix, factor):
    return {k: (v * factor if k.endswith(suffix) else v)
            for k, v in params.items()}


# name: (reference function to replace or None, its stand-in, the change of
# the parameters, the change of the configuration)
WRONG = {
    'decay_a_head': ('kda_gates', decay_a_head, None, {}),
    'no_beta': ('kda_gates', no_beta, None, {}),
    'no_k_k_term': ('delta_rule', no_outer_product, None, {}),
    'rotary_on_mla': ('causal_attention', rotary_attention, None, {}),
    # top-7 of 8 at the cell's size; 3 of 4 here
    'one_expert_too_few': (None, None, None, {'num_experts_per_token': 3}),
    'shared_expert_left_out': (None, None, ('_shared_w2_weight', 0.0), {}),
    'shared_expert_twice': (None, None, ('_shared_w2_weight', 2.0), {}),
}


@pytest.mark.parametrize('which', sorted(WRONG))
def test_a_wrong_model_is_refused_by_the_benchmarks_bounds(model, which,
                                                           monkeypatch):
    from benchmark.drivers import fit_kimi_linear as driver
    prob, _, _ = run_step(model['symbol'], model['args'], model['aux'],
                          model['tokens'], model['labels'], jnp.bfloat16)
    name, stand_in, change, config = WRONG[which]
    params = with_bias(model['args'], model['aux'])
    if change:
        params = scaled(params, *change)
    if name:
        monkeypatch.setattr(ref, name, stand_in)
    jax.clear_caches()
    wrong, _ = ref.forward(params, model['tokens'],
                           reference_config(**config))
    monkeypatch.undo()
    jax.clear_caches()
    readings = driver.forward_readings(
        prob, np.exp(np.asarray(wrong, np.float64)))
    print(which, readings)
    assert 'token_error_median' in driver.broken(readings), which
    # and past what the bf16 program reads at this size
    assert readings['token_error_median'] > 1.1 * SMALL_BF16_TOKEN_ERROR_MAX


class rounded_products(object):
    """Inside, every matrix product but a router's (one whose right side
    ends in the experts' count) has both inputs rounded to ``dtype`` and
    accumulates in float32: the plain reference in a lower precision."""

    def __init__(self, dtype, router_width):
        import jax._src.lax.lax as lax_module
        self.module, self.dtype, self.width = lax_module, dtype, router_width

    def __enter__(self):
        plain = self.plain = self.module.dot_general

        def dot_general(lhs, rhs, *args, **kwargs):
            if rhs.shape[-1] != self.width:
                lhs = lhs.astype(self.dtype).astype(jnp.float32)
                rhs = rhs.astype(self.dtype).astype(jnp.float32)
            return plain(lhs, rhs, *args, **kwargs)
        jax.clear_caches()
        self.module.dot_general = dot_general

    def __exit__(self, *exc):
        self.module.dot_general = self.plain
        jax.clear_caches()


def test_the_precision_below_the_configurations_is_refused(model):
    """The reference with float8_e4m3 products, the nearest precision
    under bf16, against itself in float32: refused, and read several times
    what bf16 products read."""
    from benchmark.drivers import fit_kimi_linear as driver
    everything = with_bias(model['args'], model['aux'])
    right = np.exp(model['log_prob'])
    read = {}
    for dtype in (jnp.bfloat16, jnp.float8_e4m3fn):
        with rounded_products(dtype, SIZES['num_experts']):
            got, _ = ref.forward(everything, model['tokens'],
                                 reference_config())
            got = np.exp(np.asarray(got, np.float64))
        read[dtype] = driver.forward_readings(got, right)
        print(jnp.dtype(dtype).name, read[dtype])
    assert 'token_error_median' in driver.broken(read[jnp.float8_e4m3fn])
    assert read[jnp.float8_e4m3fn]['token_error_median'] > \
        2.5 * read[jnp.bfloat16]['token_error_median']
    assert read[jnp.bfloat16]['token_error_median'] < \
        SMALL_BF16_TOKEN_ERROR_MAX


ADAM = dict(learning_rate=3e-4, beta1=0.9, beta2=0.95, epsilon=1e-8, wd=0.1)


def one_fit_step(model, dtype):
    """One ``Module.fit`` step with the cell's optimizer; the parameters
    after it and the fused step's Adam state."""
    data = mx.io.NDArrayIter(model['tokens'].astype(np.float32),
                             model['labels'].astype(np.float32),
                             batch_size=N)
    module = mx.mod.Module(model['symbol'], compute_dtype=dtype)
    module.fit(data, num_epoch=1, optimizer='adam',
               optimizer_params=dict(ADAM), eval_metric=['acc', 'ce'],
               arg_params={k: mx.nd.array(np.asarray(v))
                           for k, v in model['args'].items()},
               aux_params={k: mx.nd.array(np.asarray(v))
                           for k, v in model['aux'].items()})
    assert module._fused is not None
    got, aux = module.get_params()
    return ({k: v.asnumpy() for k, v in got.items()}, aux,
            {k: tuple(np.asarray(x) for x in v)
             for k, v in module.fused_optimizer_state().items()})


def host(arrays):
    return {k: np.array(v) for k, v in arrays.items()}


def test_module_fit_step_with_adam_is_the_references_update(model):
    # the cell's optimizer: wd 0.1 added to the gradient of what MXNet
    # decays (not A_log, not dt_bias), a constant rate, Module's default
    # rescale_grad of one over the batch's rows
    adam = dict(ADAM, rescale_grad=1.0 / N)
    got, aux, _ = one_fit_step(model, None)
    zeros = {k: jnp.zeros_like(v) for k, v in model['args'].items()}
    want = ref.adam_step(model['args'], model['grads'], zeros, zeros, 1, adam)
    for name, (param, _, _) in want.items():
        moved = np.asarray(param) - np.asarray(model['args'][name])
        # Adam's first step is lr x g / (|g| + epsilon): where a gradient
        # is next to nothing its rounding is the whole step
        assert rel(got[name] - np.asarray(model['args'][name]),
                   moved) < 0.1, name
    for name, value in model['aux'].items():
        if name.endswith('_expert_bias'):       # left alone by the step
            np.testing.assert_array_equal(aux[name].asnumpy(),
                                          np.asarray(value))


def test_first_update_is_held_array_by_array_and_wrong_ones_refused(model):
    from benchmark.drivers import fit_kimi_linear as driver
    adam = dict(ADAM, rescale_grad=1.0 / N)
    after, _, state = one_fit_step(model, jnp.bfloat16)

    def read(gradients=None, after=after, state=state):
        return driver.update_readings(
            ref, adam, host(model['args']),
            host(model['grads'] if gradients is None else gradients), after,
            state)[0]

    right = read()
    print('bf16 program', right)
    # the update is Adam's on the program's own gradient whatever the
    # gradient's rounding (which at this size is several times the chip's)
    assert right['update_error_worst'] < 1e-4
    after32, _, state32 = one_fit_step(model, None)
    exact = read(after=after32, state=state32)
    assert exact['gradient_error_worst'] < 2e-3
    assert exact['update_error_worst'] < 1e-4
    # a gradient taken over half the batch
    everything = with_bias(model['args'], model['aux'])
    _, half = ref.loss_and_grads(everything, model['tokens'][:1],
                                 model['labels'][:1], reference_config())
    assert 'gradient_error_median' in driver.broken(read(
        gradients=half, after=after32, state=state32))
    # an array the optimizer never moved, its moments updated all the same
    name = 'l2_kda_A_log'
    assert 'update_error_worst' in driver.broken(read(after=dict(
        after, **{name: np.asarray(model['args'][name])})))
    assert 'update_error_worst' not in driver.broken(right)
    # a state left unchanged
    still = read(after=host(model['args']),
                 state={k: (np.zeros_like(m), v) for k, (m, v) in
                        state.items()})
    assert still['gradient_error_median'] == pytest.approx(1.0)
    assert 'gradient_error_median' in driver.broken(still)
    # the reference's own gradient with float8 products
    with rounded_products(jnp.float8_e4m3fn, SIZES['num_experts']):
        _, coarse = ref.loss_and_grads(everything, model['tokens'],
                                       model['labels'], reference_config())
        coarse = host(coarse)
    given = {k: (np.asarray(g) * np.float32(adam['rescale_grad'] * 0.1) +
                 np.float32(0.1 * adam['wd'] * ref.decayed(k)) *
                 np.asarray(model['args'][k]), None)
             for k, g in coarse.items()}
    coarse = driver.update_readings(ref, adam, host(model['args']),
                                    host(model['grads']),
                                    host(model['args']), given)[0]
    print('float8 reference', coarse)
    assert 'gradient_error_median' in driver.broken(coarse)


def test_the_two_copies_of_the_reference_are_the_same_file():
    marker = '# -- everything below this line is the same in both copies'
    bodies = []
    for path in ('mxnet_tpu/models/kimi_linear_reference.py',
                 'benchmark/reference_kimi_linear.py'):
        with open(os.path.join(ROOT, path)) as f:
            head, _, body = f.read().partition(marker)
        assert body and head.lstrip().startswith('"""'), path
        bodies.append(body)
    assert bodies[0] == bodies[1]


# -- the share and the model -------------------------------------------------

def test_thirty_two_shares_with_the_shared_expert_once_are_the_uncut_layer():
    """Over the 32 shares of a layer of 64 experts (2 held a share), the
    held experts' parts added up, with the shared expert counted once,
    equal the uncut reference's layer."""
    rng = np.random.default_rng(7)
    experts, hidden, width, k = 64, HIDDEN, 24, 4
    z = draw(rng, (N * T, hidden))
    router = draw(rng, (experts, hidden), 0.3)
    w1, w3 = (draw(rng, (experts, hidden, width), 0.15) for _ in range(2))
    w2 = draw(rng, (experts, width, hidden), 0.2)
    shared = [draw(rng, (width, hidden), 0.15), draw(rng, (width, hidden),
                                                     0.15),
              draw(rng, (hidden, width), 0.2)]
    bias = draw(rng, (experts,), 0.1)
    config = dict(num_experts=experts, num_experts_per_token=k,
                  experts_held=(0, experts), moe_renormalize=True,
                  routed_scaling_factor=2.446)
    params = dict(router_weight=router, moe_expert_bias=bias,
                  experts_w1_weight=w1, experts_w3_weight=w3,
                  experts_w2_weight=w2, shared_w1_weight=shared[0],
                  shared_w3_weight=shared[1], shared_w2_weight=shared[2])
    with jax.default_matmul_precision('highest'):
        whole, load = ref.feed_forward(z, params, False, config)
        apply = get_op('SparseExperts').apply
        total, held_in_all = jnp.zeros_like(z), 0
        for first in range(0, experts, 2):
            attrs = get_op('SparseExperts').canon_attrs({
                'num_experts': experts, 'experts_held': (first, 2),
                'experts_per_tok': k, 'expert_hidden': width,
                'routed_scaling_factor': 2.446})
            part = slice(first, first + 2)
            outs, updates = apply(
                attrs, [z, router, w1[part], w3[part], w2[part], bias,
                        jnp.zeros((2,)), jnp.zeros((4,))], True, None)
            total = total + outs[0]
            held_in_all += float(updates['expert_count'][1])
            np.testing.assert_array_equal(np.asarray(updates['expert_load']),
                                          np.asarray(load[part]))
        # what every chip computes alike, once
        total = total + ref.swiglu_mlp(z, *shared)
    assert held_in_all == N * T * k
    assert rel(total, whole) < 1e-5
    # counted twice, or not at all, it is another layer
    assert rel(total + ref.swiglu_mlp(z, *shared), whole) > 0.1
    assert rel(total - ref.swiglu_mlp(z, *shared), whole) > 0.1


# -- what the step had to learn ----------------------------------------------

def test_mirror_stages_hold_the_scan_and_leave_the_gradient_alone(model):
    symbol = model['symbol']
    units = _mirror_stage_units(symbol.topo_nodes(), symbol._outputs)
    staged = [[n.op for _, n in members] for members, taken, _ in units
              if taken is not None]
    assert len(staged) == 10        # an operator and a feed-forward a layer
    assert sum('KimiDeltaAttention' in ops for ops in staged) == 4
    assert sum('FlashAttention' in ops for ops in staged) == 1
    # the shared expert's three products lie in the experts' stage
    assert all(ops.count('FullyConnected') == 3 for ops in staged
               if 'SparseExperts' in ops)
    plain = mx.sym.load_json(symbol.tojson())
    for node in plain.topo_nodes():
        node._extra_attr.pop('__mirror_stage__', None)
    _, _, want = run_step(plain, model['args'], model['aux'],
                          model['tokens'], model['labels'], jnp.float32)
    _, _, got = run_step(symbol, model['args'], model['aux'],
                         model['tokens'], model['labels'], jnp.float32)
    for name in want:
        assert rel(got[name], want[name]) < 1e-4, name


def test_the_symbols_scope_and_the_operators_reach_the_lowered_step(model):
    step = make_fit_step(model['symbol'], GradsOut(), data_names=('data',),
                         compute_dtype=jnp.bfloat16, donate=False, _raw=True)
    batch = {'data': jnp.zeros((N, T), jnp.float32),
             'softmax_label': jnp.zeros((N, T), jnp.float32)}
    text = jax.jit(step).lower(
        dict(model['args']), {}, dict(model['aux']), {}, batch,
        jnp.float32(0), jax.random.PRNGKey(0)).as_text(debug_info=True)
    # latent attention's two key-value projections and their norm stand
    # under their nodes' names, beside ``FlashAttention/<node>``
    for scope in ('FullyConnected/l3_kv_a', 'RMSNorm/l3_kv_norm',
                  'FullyConnected/l3_kv_b', 'KimiDeltaAttention/l0_kda/scan',
                  'FlashAttention/l3_att'):
        assert scope in text, scope
    # the convolutions, the gates and the output's gate run a segment at a
    # time inside the rule's outer scan: their scopes lie under ``scan``
    for nested in ('conv', 'gates', 'out_gate'):
        assert 'closed_call/%s/' % nested in text or \
            '/%s/' % nested in text, nested


def kernel_name_stacks(jaxpr, outer=()):
    """The scopes every ``pallas_call`` of ``jaxpr`` stands under: the name
    stacks along the way to it, which lowering joins into its ``op_name``."""
    from jax._src import core
    for eqn in jaxpr.eqns:
        # ``jvp(KimiDeltaAttention/l0_kda)/scan``: a transform's name is
        # a component like a scope's
        here = outer + tuple(w for w in re.split(
            '[/()]+', str(eqn.source_info.name_stack)) if w)
        if eqn.primitive.name == 'pallas_call':
            yield here
            continue
        for inner in core.jaxprs_in_params(eqn.params):
            yield from kernel_name_stacks(inner, here)


def test_the_rules_kernels_stand_under_scan_in_the_lowered_step(monkeypatch):
    """At heads of 128 channels the rule of every layer lowers to its
    Pallas kernels: for a layer the forward kernel, the same in the mirror
    stage's second forward pass and again with every chunk's state in the
    backward pass, and the backward kernel.  Each stands under its layer's
    ``scan`` and under none of the scopes that the benchmark's reduction
    takes out of ``scan`` (``fit_kimi_linear.refine_scopes``)."""
    monkeypatch.setenv('MXTPU_ASSUME_TPU', '1')
    monkeypatch.delenv('MXTPU_FORCE_PALLAS_INTERPRET', raising=False)
    sizes = dict(SIZES, linear_attn_config=dict(LINEAR, head_dim=128,
                                                num_heads=2))
    symbol = models.get_symbol('kimi_linear', seq_len=T, **sizes)
    args, aux = make_params(symbol, 0)
    step = make_fit_step(symbol, GradsOut(), data_names=('data',),
                         compute_dtype=jnp.bfloat16, donate=False, _raw=True)
    batch = {'data': jnp.zeros((N, T), jnp.float32),
             'softmax_label': jnp.zeros((N, T), jnp.float32)}
    operands = (dict(args), {}, dict(aux), {}, batch, jnp.float32(0),
                jax.random.PRNGKey(0))
    text = jax.jit(step).trace(*operands).lower(
        lowering_platforms=('tpu',)).as_text()
    assert text.count('stablehlo.custom_call @tpu_custom_call') == 4 * 4
    stacks = list(kernel_name_stacks(jax.make_jaxpr(step)(*operands).jaxpr))
    assert len(stacks) == 4 * 4
    for layer in (0, 1, 2, 4):
        of_layer = [s for s in stacks if 'l%d_kda' % layer in s]
        assert len(of_layer) == 4, layer
        for stack in of_layer:
            at = stack.index('l%d_kda' % layer)
            assert stack[at - 1] == 'KimiDeltaAttention', stack
            assert stack[at + 1] == 'scan', stack
            assert not {'conv', 'gates', 'out_gate'} & set(stack), stack


def test_the_decays_parameters_keep_their_dtype_under_bf16(model):
    step = make_fit_step(model['symbol'], GradsOut(), data_names=('data',),
                         compute_dtype=jnp.bfloat16, donate=False, _raw=True)
    batch = {'data': jnp.zeros((N, T), jnp.float32),
             'softmax_label': jnp.zeros((N, T), jnp.float32)}
    jaxpr = str(jax.make_jaxpr(step)(
        dict(model['args']), {}, dict(model['aux']), {}, batch,
        jnp.float32(0), jax.random.PRNGKey(0)))
    # A_log (4,) is cast nowhere: no bf16 array of its shape exists
    assert 'bf16[4]' not in jaxpr


def test_device_counters_reach_the_registry_only_at_a_drain(model):
    was = instrument.metrics_enabled()
    instrument.set_metrics(True)
    names = ('kda.tokens', 'kda.chunks', 'kda.decays', 'kda.decays_at_floor',
             'moe.tokens_dropped')
    try:
        before = instrument.metrics_snapshot()['counters']
        data = mx.io.NDArrayIter(
            np.tile(model['tokens'], (3, 1)).astype(np.float32),
            np.tile(model['labels'], (3, 1)).astype(np.float32),
            batch_size=N)
        seen = []
        module = mx.mod.Module(model['symbol'])
        module.fit(
            data, num_epoch=1, optimizer='adam', eval_metric=['acc', 'ce'],
            arg_params={k: mx.nd.array(np.asarray(v))
                        for k, v in model['args'].items()},
            aux_params={k: mx.nd.array(np.asarray(v))
                        for k, v in model['aux'].items()},
            batch_end_callback=lambda p: seen.append(
                instrument.counter_value('kda.tokens')))
        after = instrument.metrics_snapshot()['counters']
        moved = {k: after.get(k, 0) - before.get(k, 0) for k in names}
        # three steps of four Kimi Delta Attention layers, 3 chunks of 16
        # to a sequence of 40
        assert moved['kda.tokens'] == 3 * 4 * N * T
        assert moved['kda.chunks'] == 3 * 4 * N * 3
        # a log-decay a token and channel (4 heads of 8); under the drawn
        # weights some lie under the floor, and by far not all
        assert moved['kda.decays'] == moved['kda.tokens'] * 32
        assert 0 <= moved['kda.decays_at_floor'] < moved['kda.decays'] / 2
        assert moved['moe.tokens_dropped'] == 0
        # nothing was written between the drains: no callback saw a count
        assert seen == [before.get('kda.tokens', 0)] * 3
    finally:
        instrument.set_metrics(was)


def test_initializer_knows_the_new_operators_arrays():
    symbol = models.get_symbol('kimi_linear', seq_len=T, **SIZES)
    module = mx.mod.Module(symbol)
    module.bind(data_shapes=[('data', (N, T))],
                label_shapes=[('softmax_label', (N, T))])
    module.init_params(mx.init.Xavier())
    args, aux = module.get_params()
    assert sorted(aux) == sorted(symbol.list_auxiliary_states())
    for value in aux.values():
        assert not value.asnumpy().any()
    assert not args['l0_kda_A_log'].asnumpy().any()
    assert not args['l0_kda_dt_bias'].asnumpy().any()
    assert args['l0_kda_q_conv_weight'].asnumpy().any()


def test_the_builder_refuses_what_it_does_not_build():
    for change in ({'q_lora_rank': 64}, {'mla_use_nope': False},
                   {'num_expert_group': 2},
                   {'moe_router_activation_func': 'softmax'},
                   {'tie_word_embeddings': True}):
        with pytest.raises(ValueError):
            models.get_symbol('kimi_linear', seq_len=T,
                              **dict(SIZES, **change))
    with pytest.raises(ValueError):
        models.get_symbol('kimi_linear', seq_len=T, **dict(
            SIZES, linear_attn_config=dict(LINEAR, kda_layers=[1, 2])))


def test_gradient_arrays_reach_the_device_only_when_read(model):
    """A bound training executor has an array for every parameter's
    gradient; the fused step never reads them, so they stay unmade (602M
    parameters: 2.4e9 B of zeros on the chip otherwise), and the step by
    step path writes them as before."""
    from mxnet_tpu.ndarray import ZerosWhenRead
    data = mx.io.NDArrayIter(model['tokens'].astype(np.float32),
                             model['labels'].astype(np.float32),
                             batch_size=N)
    module = mx.mod.Module(model['symbol'])
    module.fit(data, num_epoch=1, optimizer='adam',
               optimizer_params=dict(ADAM), eval_metric=['acc', 'ce'],
               arg_params={k: mx.nd.array(np.asarray(v))
                           for k, v in model['args'].items()},
               aux_params={k: mx.nd.array(np.asarray(v))
                           for k, v in model['aux'].items()})
    assert module._fused is not None
    grads = module._exec_group.execs[0].grad_dict
    assert set(grads) == set(model['args'])
    assert all(isinstance(g, ZerosWhenRead) and g._made is None
               for g in grads.values())
    # read, it is the zeros it stands for
    assert grads['l0_b_weight'].shape == model['args']['l0_b_weight'].shape
    assert not grads['l0_b_weight'].asnumpy().any()
    assert grads['l0_b_weight']._made is not None
    # and the step by step path writes into it as into any NDArray
    data.reset()
    batch = next(iter(data))
    module.forward(batch, is_train=True)
    module.backward()
    assert grads['l0_q_weight'].asnumpy().any()
