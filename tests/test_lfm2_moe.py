"""LFM2-MoE on the CPU at a small size against its plain reference
(``mxnet_tpu/models/lfm2_moe_reference.py``): every new operator, forward
and gradients, in float32 and bf16; the whole model's log-probabilities,
loss and every parameter's gradient; one ``Module.fit`` step with Adam;
the shares of the expert layer adding up to the uncut layer; and what the
step had to learn for it (mirror stages, inputs that keep their dtype,
counters computed on the device).

Sizes: hidden 64, 2 dense + 4 expert layers, 16 experts of which 4 a token,
8 query heads over 4 key-value heads, vocabulary 512, 2 x 32 = 64 tokens.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import instrument, models
from mxnet_tpu.executor import _build_graph_fn, _mirror_stage_units
from mxnet_tpu.models import lfm2_moe_reference as ref
from mxnet_tpu.ops.registry import get_op
from mxnet_tpu.parallel.train_step import make_fit_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, T, HIDDEN, VOCAB = 2, 32, 64, 512
SIZES = dict(vocab_size=VOCAB, hidden_size=HIDDEN,
             layer_types=['conv', 'conv', 'full_attention', 'conv', 'conv',
                          'full_attention'],
             num_dense_layers=2, intermediate_size=160,
             moe_intermediate_size=48, num_experts=16, num_experts_per_tok=4,
             experts_held=(0, 16), num_attention_heads=8,
             num_key_value_heads=4, rope_theta=1e6, norm_eps=1e-5,
             norm_topk_prob=True, routed_scaling_factor=1.0)
SHAPES = {'data': (N, T), 'softmax_label': (N, T)}
DTYPES = [jnp.float32, jnp.bfloat16]
# float32 agrees to rounding; bf16 within a few of its 2^-8 steps of the
# result's size (the reference stays float32 on the same bf16-rounded inputs)
TOLERANCE = {jnp.float32: 2e-5, jnp.bfloat16: 4e-2}


def rel(got, want):
    got = np.asarray(jnp.asarray(got, jnp.float32), np.float64)
    want = np.asarray(jnp.asarray(want, jnp.float32), np.float64)
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def draw(rng, shape, scale=1.0):
    return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)


def make_params(symbol, seed):
    """Seeded arguments and auxiliary states of a model symbol."""
    rng = np.random.default_rng(seed)
    arg_shapes, _, aux_shapes = symbol.infer_shape(**SHAPES)
    args, aux = {}, {}
    for name, shape in zip(symbol.list_arguments(), arg_shapes):
        if name in SHAPES:
            continue
        if name.endswith('_gamma'):
            args[name] = 1.0 + draw(rng, shape, 0.1)
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
    keys = ('hidden_size', 'layer_types', 'num_dense_layers',
            'num_attention_heads', 'num_key_value_heads', 'num_experts',
            'num_experts_per_tok', 'experts_held', 'norm_eps',
            'norm_topk_prob', 'routed_scaling_factor', 'rope_theta')
    config = {k: SIZES[k] for k in keys}
    config.update(changes)
    return config


def tokens_and_labels(seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, VOCAB, (N, T)), rng.integers(0, VOCAB, (N, T))


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


# -- every new operator against the reference ------------------------------

def case_rms_norm(rng):
    x, w = draw(rng, (N, T, HIDDEN)), 1.0 + draw(rng, (HIDDEN,), 0.1)
    return 'RMSNorm', {'eps': 1e-5}, [x, w], [], \
        lambda x, w: ref.rms_norm(x, w, 1e-5)


def case_rotary(rng):
    x = draw(rng, (N, 8, T, 8))
    return 'RotaryEmbedding', {'theta': 1e6}, [x], [], \
        lambda x: ref.rotary(x, 1e6)


def case_swiglu(rng):
    return 'SwiGLU', {}, [draw(rng, (N * T, 160)), draw(rng, (N * T, 160))], \
        [], lambda g, u: ref.silu(g) * u


def case_silu(rng):
    return 'Activation', {'act_type': 'silu'}, [draw(rng, (N * T, 160))], \
        [], ref.silu


def case_short_conv(rng):
    bcu, kernel = draw(rng, (N, T, 3 * HIDDEN)), draw(rng, (HIDDEN, 3), 0.6)
    return 'GatedShortConv', {'kernel': 3}, [bcu, kernel], [], \
        ref.short_conv_mix


def case_attention(rng):
    q, k, v = (draw(rng, (N, heads, T, 8)) for heads in (8, 4, 4))
    return 'FlashAttention', {'causal': True, 'scale': 8 ** -0.5}, \
        [q, k, v], [], ref.causal_attention


def case_experts(rng, held=(4, 8), favour=0.0, experts=16):
    config = reference_config(experts_held=held, num_experts=experts)
    z = draw(rng, (N * T, HIDDEN))
    router = draw(rng, (experts, HIDDEN), 0.3)
    w1, w3 = (draw(rng, (held[1], HIDDEN, 48), 0.125) for _ in range(2))
    w2 = draw(rng, (held[1], 48, HIDDEN), 0.14)
    bias = draw(rng, (experts,), 0.1) \
        .at[held[0]:held[0] + held[1]].add(favour)
    aux = [bias, jnp.zeros((held[1],)), jnp.zeros((4,))]
    attrs = {'num_experts': experts, 'experts_held': held,
             'experts_per_tok': 4, 'expert_hidden': 48}
    return 'SparseExperts', attrs, [z, router, w1, w3, w2], aux, \
        lambda z, r, a, b, c: ref.expert_layer(z, r, bias, a, b, c, config)[0]


def case_experts_in_the_buffer(rng):
    # 3 of 32 experts held: a buffer of 96 rows for 256 assignments, of
    # which some 24 land here
    return case_experts(rng, (4, 3), experts=32)


def case_experts_over_the_buffer(rng):
    # the selection bias sends nearly every token to all three held experts
    return case_experts(rng, (4, 3), favour=1.0, experts=32)


def case_experts_one_held_receives_nothing(rng):
    # the selection bias keeps every token off the second held expert
    return case_experts(rng, (4, 3), favour=jnp.array([0.0, -100.0, 0.0]),
                        experts=32)


def case_experts_a_hundredth_of_the_layer(rng):
    # and nearly every token off all three: 3 of the 256 assignments land
    return case_experts(rng, (4, 3), favour=-0.15, experts=32)


LADDER = (48, 96, 280)      # of 3 held experts of 32, rows from multiples of 8


def experts_with_loads(which, loads):
    """A case of 3 held experts of 32 that receive exactly ``loads`` of the
    64 tokens: the router's rows are unit vectors, so a token's first 32
    features are its logits: small for the absent experts, and a held
    expert's +8 for the tokens it is to receive and -8 for every other."""
    def case(rng):
        name, attrs, inputs, aux, reference = case_experts(rng, (4, 3),
                                                           experts=32)
        z = np.array(inputs[0])
        z[:, :32] *= 0.1        # every other expert scores about a half
        for held, load in enumerate(loads):
            z[:, 4 + held] = -8.0
            z[rng.permutation(N * T)[:load], 4 + held] = 8.0
        inputs[0], inputs[1] = jnp.asarray(z), jnp.eye(32, HIDDEN)
        return name, attrs, inputs, aux, reference
    case.__name__ = 'case_experts_' + which
    return case


# name: (each held expert's load, which of ``LADDER`` is the smallest that
# holds them rounded up to 8)
ON_THE_LADDER = {
    'nothing_held': ((0, 0, 0), 0),
    'filling_the_first_rung': ((16, 16, 16), 0),
    'one_row_over_the_first_rung': ((17, 16, 16), 1),
    'one_expert_takes_every_token': ((64, 0, 0), 1),
    'filling_the_second_rung': ((30, 28, 30), 1),
    'one_row_over_the_second_rung': ((33, 32, 32), 2),
    'every_held_expert_takes_every_token': ((64, 64, 64), 2),
}
LADDER_CASES = {which: experts_with_loads(which, loads)
                for which, (loads, _) in ON_THE_LADDER.items()}

CASES = [case_rms_norm, case_rotary, case_swiglu, case_silu, case_short_conv,
         case_attention, case_experts, case_experts_in_the_buffer,
         case_experts_over_the_buffer, case_experts_one_held_receives_nothing,
         case_experts_a_hundredth_of_the_layer] + list(LADDER_CASES.values())


def apply_op(name, attrs, inputs, aux):
    op = get_op(name)
    return op.apply(op.canon_attrs(attrs), list(inputs) + list(aux), True,
                    None)


@pytest.mark.parametrize('dtype', DTYPES, ids=['float32', 'bfloat16'])
@pytest.mark.parametrize('case', CASES, ids=lambda c: c.__name__[5:])
def test_operator_forward_and_gradients_agree_with_the_reference(case, dtype):
    rng = np.random.default_rng(11)
    name, attrs, inputs, aux, reference = case(rng)
    keep = get_op(name).keep_dtype
    in_names = get_op(name).input_names(get_op(name).canon_attrs(attrs))
    # what a mixed-precision step hands the operator
    cast = [x if n in keep else x.astype(dtype)
            for n, x in zip(in_names, inputs)]
    rounded = [x.astype(jnp.float32) for x in cast]
    with jax.default_matmul_precision('highest'):
        want = reference(*rounded)
    cotangent = draw(rng, want.shape)

    def program(*xs):
        out = apply_op(name, attrs, xs, aux)[0][0]
        return jnp.sum(out.astype(jnp.float32) * cotangent), out

    def plain(*xs):
        with jax.default_matmul_precision('highest'):
            return jnp.sum(reference(*xs) * cotangent)

    which = tuple(range(len(inputs)))
    grads, out = jax.grad(program, which, has_aux=True)(*cast)
    grads_want = jax.grad(plain, which)(*rounded)
    limit = TOLERANCE[dtype]
    assert out.dtype == dtype
    assert rel(out, want) < limit
    for got, wanted in zip(grads, grads_want):
        assert bool(jnp.isfinite(got).all())
        assert rel(got, wanted) < 2 * limit


SYMBOLS = {
    'RMSNorm': lambda d: mx.sym.RMSNorm(d, eps=1e-5, name='n'),
    'RotaryEmbedding': lambda d: mx.sym.RotaryEmbedding(d, theta=1e6,
                                                        name='r'),
    'SwiGLU': lambda d: mx.sym.SwiGLU(d, mx.sym.Variable('up'), name='s'),
    'Activation': lambda d: mx.sym.Activation(d, act_type='silu', name='a'),
    'GatedShortConv': lambda d: mx.sym.GatedShortConv(d, kernel=3, name='c'),
    'FlashAttention': lambda d: mx.sym.FlashAttention(
        d, mx.sym.Variable('key'), mx.sym.Variable('value'), causal=True,
        name='att'),
    'SparseExperts': lambda d: mx.sym.SparseExperts(
        d, num_experts=16, experts_held=(4, 8), experts_per_tok=4,
        expert_hidden=48, name='moe'),
}
INFERRED = {
    'RMSNorm': ({'data': (4, 6, 64)}, {'n_gamma': (64,)}, (4, 6, 64)),
    'RotaryEmbedding': ({'data': (2, 8, 32, 8)}, {}, (2, 8, 32, 8)),
    'SwiGLU': ({'data': (64, 160), 'up': (64, 160)}, {}, (64, 160)),
    'Activation': ({'data': (64, 160)}, {}, (64, 160)),
    'GatedShortConv': ({'data': (2, 32, 192)}, {'c_weight': (64, 3)},
                       (2, 32, 64)),
    'FlashAttention': ({'data': (2, 8, 32, 8), 'key': (2, 4, 32, 8),
                        'value': (2, 4, 32, 8)}, {}, (2, 8, 32, 8)),
    'SparseExperts': ({'data': (64, 64)},
                      {'moe_router_weight': (16, 64),
                       'moe_w1_weight': (8, 64, 48),
                       'moe_w3_weight': (8, 64, 48),
                       'moe_w2_weight': (8, 48, 64),
                       'moe_expert_bias': (16,), 'moe_expert_load': (8,),
                       'moe_expert_count': (4,)}, (64, 64)),
}


@pytest.mark.parametrize('name', sorted(SYMBOLS))
def test_operator_shape_inference_and_json_round_trip(name):
    symbol = SYMBOLS[name](mx.sym.Variable('data'))
    given, inferred, out = INFERRED[name]
    arg_shapes, out_shapes, aux_shapes = symbol.infer_shape(**given)
    found = dict(zip(symbol.list_arguments(), arg_shapes))
    found.update(zip(symbol.list_auxiliary_states(), aux_shapes))
    for key, shape in inferred.items():
        assert tuple(found[key]) == shape, key
    assert tuple(out_shapes[0]) == out
    text = symbol.tojson()
    again = mx.sym.load_json(text)
    assert again.tojson() == text
    assert again.infer_shape(**given)[1] == out_shapes


# -- the whole model --------------------------------------------------------

@pytest.fixture(scope='module')
def model():
    symbol = models.get_symbol('lfm2_moe', seq_len=T, **SIZES)
    args, aux = make_params(symbol, 3)
    tokens, labels = tokens_and_labels(5)
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
    names = set(symbol.list_arguments()) - set(SHAPES)
    names |= {n for n in symbol.list_auxiliary_states()
              if n.endswith('_expert_bias')}
    assert names == set(ref.param_names(reference_config()))


def test_model_float32_agrees_tightly(model):
    prob, aux, grads = run_step(model['symbol'], model['args'], model['aux'],
                                model['tokens'], model['labels'], jnp.float32)
    assert np.abs(np.log(prob) - model['log_prob']).max() < 2e-4
    picked = np.log(prob)[np.arange(N * T), model['labels'].reshape(-1)]
    assert abs(-picked.sum() - model['loss']) < 1e-3 * model['loss']
    assert set(grads) == set(model['grads'])
    for name, want in model['grads'].items():
        assert rel(grads[name], want) < 1e-4, name
    for layer, load in model['load'].items():
        np.testing.assert_array_equal(
            np.asarray(aux['l%d_moe_expert_load' % layer]), np.asarray(load))
        np.testing.assert_array_equal(
            np.asarray(aux['l%d_moe_expert_count' % layer]),
            [N * T * 4, N * T * 4, 0, 0])


def test_model_bf16_agrees_within_the_benchmarks_bounds(model):
    from benchmark.drivers import fit_lm
    prob, aux, grads = run_step(model['symbol'], model['args'], model['aux'],
                                model['tokens'], model['labels'],
                                jnp.bfloat16)
    assert fit_lm.broken(fit_lm.forward_readings(
        prob, np.exp(model['log_prob']))) == []
    picked = np.log(prob)[np.arange(N * T), model['labels'].reshape(-1)]
    assert abs(-picked.sum() - model['loss']) < 0.02 * model['loss']
    for name, want in model['grads'].items():
        assert grads[name].dtype == jnp.float32
        # a router's gradient turns on the few tokens whose choice bf16
        # tipped; every other array's follows the reference's
        assert rel(grads[name], want) < (1.0 if 'router' in name else 0.35), \
            name
    for layer, load in model['load'].items():
        assert float(aux['l%d_moe_expert_count' % layer][2]) == 0


# -- what the benchmark's ``correct`` refuses -------------------------------
# Each wrong model goes through ``benchmark/drivers/fit_lm.py``'s own
# measures and limits (``forward_readings``, ``update_readings``,
# ``broken``).  Readings here, on the CPU at this file's size (PR 28; a
# scale for the limits, not a device number; the chip's at the cell's size
# are beside the limits and in PERF.md):
#                                   error   median  agreement
#   bf16 program                    0.049 to 0.082, 0.030 to 0.037, 0.9966 to 0.9988
#   bf16, reference routes top-3    0.238   0.215   0.971
#   bf16, reference without bias    0.245   0.226   0.969
#   bf16, reference's conv shifted  1.419   1.408   -0.012
#   reference, bf16 products        0.083   0.029   0.9965
#   reference, float8_e4m3 products 0.351   0.328   0.938
# and of the first update, median and worst ``gradient_error``: bf16
# program 0.115 and 0.329 (``update_error`` 9e-7); the reference's gradient
# with float8_e4m3 products against its own 0.561 and 0.881; a gradient
# over half the batch 0.952 and 1.163.

WRONG = {
    'top3': lambda p: (p, reference_config(num_experts_per_tok=3)),
    'no_bias': lambda p: ({k: (jnp.zeros_like(v)
                               if k.endswith('_expert_bias') else v)
                           for k, v in p.items()}, reference_config()),
    # a leading zero tap: c_t = sum_j k_j g_{t-1-j}
    'conv_shifted': lambda p: ({k: (jnp.pad(v, ((0, 0), (1, 0)))
                                    if k.endswith('_conv_weight') else v)
                                for k, v in p.items()}, reference_config()),
}


@pytest.mark.parametrize('which', sorted(WRONG))
def test_a_wrong_model_is_refused_by_the_benchmarks_bounds(model, which):
    from benchmark.drivers import fit_lm
    prob, _, _ = run_step(model['symbol'], model['args'], model['aux'],
                          model['tokens'], model['labels'], jnp.bfloat16)
    params, config = WRONG[which](with_bias(model['args'], model['aux']))
    wrong, _ = ref.forward(params, model['tokens'], config)
    readings = fit_lm.forward_readings(prob, np.exp(np.asarray(wrong,
                                                               np.float64)))
    print(which, readings)
    assert 'token_error_median' in fit_lm.broken(readings)
    assert readings['token_error_median'] > \
        1.5 * fit_lm.LIMITS['token_error_median'][0]


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


def test_the_precision_below_the_configurations_is_refused_by_one_limit(
        model):
    """The reference with float8_e4m3 products, the nearest precision
    under bf16, against itself in float32: refused, and by the median
    alone; with bf16 products it passes."""
    from benchmark.drivers import fit_lm
    everything = with_bias(model['args'], model['aux'])
    right = np.exp(model['log_prob'])
    for dtype, refused in ((jnp.bfloat16, []),
                           (jnp.float8_e4m3fn, ['token_error_median'])):
        with rounded_products(dtype, SIZES['num_experts']):
            got, _ = ref.forward(everything, model['tokens'],
                                 reference_config())
            got = np.exp(np.asarray(got, np.float64))
        readings = fit_lm.forward_readings(got, right)
        print(jnp.dtype(dtype).name, readings)
        assert fit_lm.broken(readings) == refused


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


def test_module_fit_step_with_adam_is_the_references_update(model):
    # the cell's optimizer: wd 0.1 added to the gradient, a constant rate,
    # Module's default rescale_grad of one over the batch's rows
    adam = dict(ADAM, rescale_grad=1.0 / N)
    got, aux, _ = one_fit_step(model, None)
    zeros = {k: jnp.zeros_like(v) for k, v in model['args'].items()}
    want = ref.adam_step(model['args'], model['grads'], zeros, zeros, 1, adam)
    for name, (param, _, _) in want.items():
        moved = np.asarray(param) - np.asarray(model['args'][name])
        # Adam's first step is lr x g / (|g| + epsilon): where a gradient
        # is next to nothing its rounding is the whole step
        assert rel(got[name] - np.asarray(model['args'][name]),
                   moved) < 1e-2, name
    for name, value in model['aux'].items():
        if name.endswith('_expert_bias'):       # left alone by the step
            np.testing.assert_array_equal(aux[name].asnumpy(),
                                          np.asarray(value))


def host(arrays):
    return {k: np.array(v) for k, v in arrays.items()}


def test_first_update_is_held_array_by_array_and_wrong_ones_refused(model):
    from benchmark.drivers import fit_lm
    adam = dict(ADAM, rescale_grad=1.0 / N)
    after, _, state = one_fit_step(model, jnp.bfloat16)

    def read(gradients=None, after=after, state=state):
        return fit_lm.update_readings(
            ref, adam, host(model['args']),
            host(model['grads'] if gradients is None else gradients), after,
            state)[0]

    right = read()
    print('bf16 program', right)
    assert fit_lm.broken(right) == []
    # float32: the backward pass to rounding, the update exact
    after32, _, state32 = one_fit_step(model, None)
    exact = read(after=after32, state=state32)
    assert exact['gradient_error_worst'] < 1e-3
    assert exact['update_error_worst'] < 1e-4
    # a gradient taken over half the batch
    everything = with_bias(model['args'], model['aux'])
    _, half = ref.loss_and_grads(everything, model['tokens'][:1],
                                 model['labels'][:1], reference_config())
    wrong = read(gradients=half)
    print('half the batch', wrong)
    assert 'gradient_error_median' in fit_lm.broken(wrong)
    # an array the optimizer never moved, its moments updated all the same
    name = 'l3_experts_w2_weight'
    assert fit_lm.broken(read(after=dict(
        after, **{name: np.asarray(model['args'][name])}))) == \
        ['update_error_worst']
    # an array whose gradient never arrived (a backward rule that gives
    # nothing back): Adam is given the decay alone
    decay = np.asarray(model['args'][name]) * np.float32(ADAM['wd'] * 0.1)
    assert 'gradient_error_worst' in fit_lm.broken(read(state=dict(
        state, **{name: (decay, state[name][1])})))
    # a state left unchanged
    still = read(after=host(model['args']),
                 state={k: (np.zeros_like(m), v) for k, (m, v) in
                        state.items()})
    assert still['gradient_error_median'] == pytest.approx(1.0)
    assert 'gradient_error_median' in fit_lm.broken(still)
    # the reference's own gradient with float8 products: what the limits
    # leave to the forward measures
    with rounded_products(jnp.float8_e4m3fn, SIZES['num_experts']):
        _, coarse = ref.loss_and_grads(everything, model['tokens'],
                                       model['labels'], reference_config())
        coarse = host(coarse)
    given = {k: (np.asarray(g) * np.float32(adam['rescale_grad'] * 0.1) +
                 np.float32(0.1 * adam['wd']) * np.asarray(model['args'][k]),
                 None) for k, g in coarse.items()}
    coarse = fit_lm.update_readings(ref, adam, host(model['args']),
                                    host(model['grads']),
                                    host(model['args']), given)[0]
    print('float8 reference', coarse)
    assert 'gradient_error_median' in fit_lm.broken(coarse)


def test_the_two_copies_of_the_reference_are_the_same_file():
    marker = '# -- everything below this line is the same in both copies'
    bodies = []
    for path in ('mxnet_tpu/models/lfm2_moe_reference.py',
                 'benchmark/reference_lfm2_moe.py'):
        with open(os.path.join(ROOT, path)) as f:
            head, _, body = f.read().partition(marker)
        assert body and head.lstrip().startswith('"""'), path
        bodies.append(body)
    assert bodies[0] == bodies[1]


# -- the share and the model ------------------------------------------------

def expert_parts(rng_seed, bias=None):
    rng = np.random.default_rng(rng_seed)
    z = draw(rng, (N * T, HIDDEN))
    router = draw(rng, (16, HIDDEN), 0.3)
    w1, w3 = (draw(rng, (16, HIDDEN, 48), 0.125) for _ in range(2))
    w2 = draw(rng, (16, 48, HIDDEN), 0.14)
    bias = draw(rng, (16,), 0.1) if bias is None else bias
    return z, router, w1, w3, w2, bias


def share_of(first, count, z, router, w1, w3, w2, bias):
    attrs = {'num_experts': 16, 'experts_held': (first, count),
             'experts_per_tok': 4, 'expert_hidden': 48}
    part = slice(first, first + count)
    aux = [bias, jnp.zeros((count,)), jnp.zeros((4,))]
    outs, updates = apply_op('SparseExperts', attrs,
                             [z, router, w1[part], w3[part], w2[part]], aux)
    return outs[0], updates


def test_eight_shares_of_two_experts_add_up_to_the_uncut_layer():
    z, router, w1, w3, w2, bias = expert_parts(21)
    uncut = reference_config(experts_held=(0, 16))
    cotangent = draw(np.random.default_rng(22), z.shape)

    def shares(z):
        return sum(share_of(first, 2, z, router, w1, w3, w2, bias)[0]
                   for first in range(0, 16, 2))

    def whole(z):
        with jax.default_matmul_precision('highest'):
            return ref.expert_layer(z, router, bias, w1, w3, w2, uncut)[0]

    assert rel(shares(z), whole(z)) < 2e-5
    by_input = jax.grad(lambda z: jnp.sum(shares(z) * cotangent))(z)
    by_input_whole = jax.grad(lambda z: jnp.sum(whole(z) * cotangent))(z)
    assert rel(by_input, by_input_whole) < 2e-5
    held = sum(float(share_of(first, 2, z, router, w1, w3, w2,
                              bias)[1]['expert_count'][1])
               for first in range(0, 16, 2))
    assert held == N * T * 4        # every assignment lands on one share


@pytest.mark.parametrize('held', [(5, 1), (4, 4), (0, 16)],
                         ids=['alone', 'among_four', 'among_all'])
def test_one_expert_taking_every_token_drops_none(held):
    # a selection bias so skewed that expert 5 is every token's first choice
    bias = jnp.zeros((16,)).at[5].set(100.0)
    z, router, w1, w3, w2, bias = expert_parts(23, bias)
    out, updates = share_of(held[0], held[1], z, router, w1, w3, w2, bias)
    config = reference_config(experts_held=held)
    part = slice(held[0], held[0] + held[1])
    with jax.default_matmul_precision('highest'):
        want, load = ref.expert_layer(z, router, bias, w1[part], w3[part],
                                      w2[part], config)
    assert float(updates['expert_load'][5 - held[0]]) == N * T
    np.testing.assert_array_equal(np.asarray(updates['expert_load']),
                                  np.asarray(load))
    assert float(updates['expert_count'][2]) == 0      # tokens dropped
    assert rel(out, want) < 2e-5


def share_so_far(histogram):
    """(observations, their sum) of the histogram ``moe.<histogram>``."""
    h = instrument.metrics_snapshot().get('histograms', {}).get(
        'moe.' + histogram, {'count': 0, 'sum': 0.0})
    return np.array([h['count'], h['sum']])


def drained(name, attrs, inputs, load, histogram):
    """What one drain of the operator's counters after a step of ``load``
    adds to ``moe.<histogram>``: (observations, their sum)."""
    from mxnet_tpu.ops import lm
    was = instrument.metrics_enabled()
    instrument.set_metrics(True)
    try:
        before = share_so_far(histogram)
        lm._sparse_experts_counters(
            {'expert_load': np.asarray(load), 'expert_count': np.zeros(4)},
            None, get_op(name).canon_attrs(attrs), [inputs[0].shape])
        return share_so_far(histogram) - before
    finally:
        instrument.set_metrics(was)


def test_the_buffer_is_a_ladder_and_a_step_on_its_last_rung_is_counted():
    from mxnet_tpu.ops import lm
    # (the buffers, smallest first, the four-share one among them and the
    # alignment) of the cell's layer, of an uncut layer and of the cases at
    # this file's size
    assert lm._room(16384 * 4, 8, 64) == \
        ((16384, 32768, 65536 + 8 * 512), 32768, 512)
    assert lm._room(N * T * 4, 16, 16) == ((320,), 320, 4)
    assert lm._room(N * T * 4, 3, 32) == (LADDER, 96, 8)
    for case, over in ((case_experts_in_the_buffer, False),
                       (case_experts_over_the_buffer, True)):
        name, attrs, inputs, aux, _ = case(np.random.default_rng(11))
        routed, held, dropped, steps_over = np.asarray(
            apply_op(name, attrs, inputs, aux)[1]['expert_count'])
        assert routed == N * T * 4 and dropped == 0
        assert (held > 96) == over and steps_over == float(over)


@pytest.mark.parametrize('which', sorted(ON_THE_LADDER))
def test_a_step_takes_the_smallest_rung_that_holds_it(which):
    loads, rung = ON_THE_LADDER[which]
    name, attrs, inputs, aux, _ = LADDER_CASES[which](
        np.random.default_rng(11))
    out, updates = apply_op(name, attrs, inputs, aux)
    np.testing.assert_array_equal(np.asarray(updates['expert_load']), loads)
    routed, held, dropped, last_rung = np.asarray(updates['expert_count'])
    assert (routed, held, dropped) == (N * T * 4, sum(loads), 0)
    assert last_rung == float(rung == 2)
    if not sum(loads):
        assert not np.asarray(out[0]).any()
    # the drain says which rung that was, against the four shares' 96 rows
    count, share = drained(name, attrs, inputs, updates['expert_load'],
                           'rows_copied_share')
    assert count == 1 and share == pytest.approx(LADDER[rung] / 96)


def test_the_lowered_layer_sorts_once():
    # the assignments by expert; a second sort gave each assignment its row
    # while the collect was made from the assignments' side
    name, attrs, inputs, aux, _ = case_experts_in_the_buffer(
        np.random.default_rng(11))
    cotangent = draw(np.random.default_rng(12), inputs[0].shape)
    lowered = jax.jit(jax.grad(
        lambda *xs: experts_loss(xs, attrs, aux, cotangent)[0],
        tuple(range(5)))).lower(*inputs).as_text()
    assert lowered.count('stablehlo.sort') == 1


# -- the products run over the rows that hold something ---------------------

def case_experts_filling_the_buffer(rng):
    # expert 5, held alone, is every token's first choice: 64 assignments
    # into a buffer of 64 rows
    name, attrs, inputs, aux, reference = case_experts(rng, (5, 1))
    aux[0] = jnp.zeros((16,)).at[5].set(100.0)
    return name, attrs, inputs, aux, reference


def experts_loss(inputs, attrs, aux, cotangent):
    out = apply_op('SparseExperts', attrs, inputs, aux)[0][0]
    return jnp.sum(out * cotangent), out


@pytest.mark.parametrize('case, rows, full', [
    (case_experts_in_the_buffer, 48, False),
    (case_experts_a_hundredth_of_the_layer, 48, False),
    (case_experts_over_the_buffer, 280, False),
    (case_experts_filling_the_buffer, 64, True)] + [
    (LADDER_CASES[which], LADDER[rung], which.startswith('filling'))
    for which, (_, rung) in sorted(ON_THE_LADDER.items())],
    ids=['in_the_buffer', 'a_hundredth', 'over_the_buffer', 'filling_it'] +
    sorted(ON_THE_LADDER))
def test_grouped_products_are_given_each_experts_aligned_rows_and_no_more(
        monkeypatch, case, rows, full):
    from mxnet_tpu.ops import lm
    name, attrs, inputs, aux, _ = case(np.random.default_rng(11))
    given, plain = [], lm.grouped_matmul

    def recording(lhs, rhs, group_sizes):
        # a callback, so that a slice of the last rung, which is traced even
        # eagerly (a checkpoint inside a scan), is seen with its numbers too
        jax.debug.callback(
            lambda sizes, rows=lhs.shape[0]: given.append(
                (rows, np.asarray(sizes))), group_sizes)
        return plain(lhs, rhs, group_sizes)

    monkeypatch.setattr(lm, 'grouped_matmul', recording)
    cotangent = draw(np.random.default_rng(12), inputs[0].shape)
    # eagerly, so that a ``cond``'s branch sees numbers: forward, then
    # forward and backward (which computes the taken branch again)
    with jax.disable_jit():
        load = np.asarray(apply_op(name, attrs, inputs, aux)[1]['expert_load'])
        jax.effects_barrier()
        forward = list(given)
        jax.grad(lambda *xs: experts_loss(xs, attrs, aux, cotangent)[0],
                 tuple(range(5)))(*inputs)
        jax.effects_barrier()
    rooms, _, align = lm._room(N * T * 4, attrs['experts_held'][1],
                               attrs['num_experts'])
    want = np.ceil(load / align) * align
    assert len(given) >= 6
    if len(rooms) > 1 and rows == rooms[-1]:
        # the ladder's last rung runs the rung before it's rows at a time
        # (PR 34): every product is given one slice's rows, in groups that
        # start on a tile's first row, and over the slices each expert is
        # given its aligned rows and no more, by each of the three products
        for buffer_rows, sizes in given:
            assert buffer_rows == rooms[-2]
            assert sizes.sum() <= buffer_rows and not (sizes % align).any()
        assert len(forward) == 3 * -(-rows // rooms[-2])
        np.testing.assert_array_equal(
            np.sum([sizes for _, sizes in forward], axis=0), 3 * want)
    else:
        for buffer_rows, sizes in given:
            assert buffer_rows == rows
            np.testing.assert_array_equal(sizes, want)
    assert want.sum() <= rows and (want.sum() == rows) == full
    # and the share the drain reports is these rows over that buffer
    count, share = drained(name, attrs, inputs, load, 'rows_visited_share')
    assert count == 1 and share == pytest.approx(want.sum() / rows)


def poisoned(plain):
    """``plain`` with every row past the groups' last, which the chip's
    product does not write, read back as NaN: in the product and in its
    transpose by the rows.  The transpose by the weights reads the groups'
    rows alone, as the chip's does."""
    def within(rows, sizes, beyond=0):
        return jnp.where((jnp.arange(rows.shape[0]) < sizes.sum())[:, None],
                         rows, beyond)

    def spoil(rows, sizes):
        return within(rows, sizes, jnp.nan)

    @jax.custom_vjp
    def product(lhs, rhs, sizes):
        return spoil(plain(within(lhs, sizes), rhs, sizes), sizes)

    def forward(lhs, rhs, sizes):
        return product(lhs, rhs, sizes), (lhs, rhs, sizes)

    def backward(kept, g):
        lhs, rhs, sizes = kept
        by_rows, by_weights = jax.vjp(
            lambda l, r: plain(l, r, sizes), within(lhs, sizes), rhs)[1](
                within(g, sizes))
        return spoil(by_rows, sizes), by_weights, None

    product.defvjp(forward, backward)
    return product


@pytest.mark.parametrize('dtype', DTYPES, ids=['float32', 'bfloat16'])
@pytest.mark.parametrize('case', [
    case_experts_in_the_buffer, case_experts_over_the_buffer,
    case_experts_a_hundredth_of_the_layer,
    LADDER_CASES['nothing_held'], LADDER_CASES['one_row_over_the_first_rung'],
    LADDER_CASES['one_row_over_the_second_rung']],
    ids=['in_the_buffer', 'over_the_buffer', 'a_hundredth', 'nothing_held',
         'on_the_second_rung', 'on_the_last_rung'])
def test_rows_the_products_do_not_write_reach_no_output_and_no_gradient(
        monkeypatch, case, dtype):
    """The CPU's product writes zeros past its groups and would hide what
    the chip's leaves there."""
    from mxnet_tpu.ops import lm
    name, attrs, inputs, aux, _ = case(np.random.default_rng(11))
    inputs = [x if i == 1 else x.astype(dtype) for i, x in enumerate(inputs)]
    cotangent = draw(np.random.default_rng(12), inputs[0].shape)

    def run():
        grads, out = jax.grad(
            lambda *xs: experts_loss(xs, attrs, aux, cotangent),
            tuple(range(5)), has_aux=True)(*inputs)
        return [out] + list(grads)

    clean = run()
    spoilt = []
    plain = lm.grouped_matmul

    def counting(lhs, rhs, sizes):
        spoilt.append(lhs.shape[0])
        return poisoned(plain)(lhs, rhs, sizes)

    monkeypatch.setattr(lm, 'grouped_matmul', counting)
    dirty = run()
    assert len(spoilt) >= 6
    for got, want in zip(dirty, clean):
        assert bool(jnp.isfinite(got).all())
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


# -- what the step had to learn ---------------------------------------------

def test_mirror_stages_group_the_blocks_and_leave_the_gradient_alone(model):
    symbol = model['symbol']
    units = _mirror_stage_units(symbol.topo_nodes(), symbol._outputs)
    stages = [u for u in units if u[1] is not None]
    # four convolution sub-blocks and six feed-forward sub-blocks
    assert len(stages) == 4 + 6
    for members, taken, given in stages:
        assert len(members) > 1 and taken and len(given) == 1
    plain = models.get_symbol('lfm2_moe', seq_len=T, **SIZES)
    for node in plain.topo_nodes():
        node._extra_attr.pop('__mirror_stage__', None)
    assert all(u[1] is None for u in _mirror_stage_units(
        plain.topo_nodes(), plain._outputs))
    staged = run_step(symbol, model['args'], model['aux'], model['tokens'],
                      model['labels'], jnp.float32)[2]
    unstaged = run_step(plain, model['args'], model['aux'], model['tokens'],
                        model['labels'], jnp.float32)[2]
    for name in staged:
        assert rel(staged[name], unstaged[name]) < 1e-5, name
    batch = dict(data=jnp.zeros((N, T)), softmax_label=jnp.zeros((N, T)))
    for graph, count in ((symbol, 10), (plain, 0)):
        traced = jax.make_jaxpr(lambda p, g=graph: _build_graph_fn(
            g, True, _count=False)(dict(p, **batch), model['aux'],
                                   jax.random.PRNGKey(0)))(model['args'])
        assert str(traced).count('prevent_cse=True') == count


def test_token_ids_and_the_router_keep_their_dtype_under_bf16(model):
    # ids above 256 are not bf16 numbers: 257 would read 256
    symbol = models.get_symbol('lfm2_moe', seq_len=T, **dict(
        SIZES, layer_types=['conv'], num_dense_layers=1))
    args, aux = make_params(symbol, 4)
    tokens = np.full((N, T), 257)
    prob, _, _ = run_step(symbol, args, aux, tokens, tokens, jnp.bfloat16)
    want, _ = ref.forward(args, tokens, reference_config(
        layer_types=['conv'], num_dense_layers=1))
    other, _ = ref.forward(args, np.full((N, T), 256), reference_config(
        layer_types=['conv'], num_dense_layers=1))
    near = np.abs(np.log(prob) - np.asarray(want)).mean()
    far = np.abs(np.log(prob) - np.asarray(other)).mean()
    assert near < 0.05 < far
    assert get_op('Embedding').keep_dtype == ('data',)
    assert get_op('SparseExperts').keep_dtype == ('router_weight',)


def test_device_counters_reach_the_registry_only_at_a_drain(model):
    was = instrument.metrics_enabled()
    instrument.set_metrics(True)
    try:
        before = instrument.metrics_snapshot()['counters']
        visited_before = tuple(share_so_far('rows_visited_share'))
        visited_seen = []
        copied_before = share_so_far('rows_copied_share')
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
            batch_end_callback=lambda p: (
                seen.append(instrument.counter_value('moe.assignments')),
                visited_seen.append(
                    tuple(share_so_far('rows_visited_share')))))
        after = instrument.metrics_snapshot()
        moved = {k: after['counters'].get(k, 0) - before.get(k, 0)
                 for k in ('moe.assignments', 'moe.assignments_held',
                           'moe.tokens_dropped')}
        # three steps of four expert layers; every expert is held here
        assert moved == {'moe.assignments': 3 * 4 * N * T * 4,
                         'moe.assignments_held': 3 * 4 * N * T * 4,
                         'moe.tokens_dropped': 0}
        # nothing was written between the drains: no callback saw a count
        assert seen == [before.get('moe.assignments', 0)] * 3
        assert 'moe.tokens_dropped' in after['counters']
        assert after['counters']['moe.steps_over_capacity'] == \
            before.get('moe.steps_over_capacity', 0)
        uneven = after['histograms']['moe.load_max_over_mean']
        assert uneven['count'] >= 4 and uneven['sum'] / uneven['count'] > 1
        # the rows the last step's products visited over the buffer's 320
        # (every expert held, each expert's rows from a multiple of 4),
        # one observation a layer and drain, none between the drains
        assert visited_seen == [visited_before] * 3
        count, share = share_so_far('rows_visited_share') - visited_before
        loads = [aux.asnumpy() for name, aux in module.get_params()[1].items()
                 if name.endswith('_expert_load')]
        assert count >= 4 and count % 4 == 0
        assert share / count == pytest.approx(np.mean(
            [(np.ceil(load / 4) * 4).sum() / 320 for load in loads]))
        assert 0.8 < share / count <= 1
        # a layer whose ladder is one buffer ran in it: 1 at every drain
        np.testing.assert_array_equal(
            share_so_far('rows_copied_share') - copied_before, [count, count])
    finally:
        instrument.set_metrics(was)


def test_the_counters_are_handed_the_nodes_attributes_and_input_shapes():
    symbol = models.get_symbol('lfm2_moe', seq_len=T, **SIZES)
    module = mx.mod.Module(symbol)
    module.bind(data_shapes=[('data', (N, T))],
                label_shapes=[('softmax_label', (N, T))])
    module._aux_counted = module._find_aux_counted()
    assert len(module._aux_counted) == 4
    for (write, local, names, attrs, _, _), shapes in zip(
            module._aux_counted, module._aux_counted_shapes()):
        assert write is get_op('SparseExperts').aux_counters
        assert local == ['expert_bias', 'expert_load', 'expert_count']
        assert names == [names[0][:-len('expert_bias')] + n for n in local]
        assert attrs['experts_per_tok'] == 4 and attrs['num_experts'] == 16
        assert [tuple(s) for s in shapes] == [
            (N * T, HIDDEN), (16, HIDDEN), (16, HIDDEN, 48), (16, HIDDEN, 48),
            (16, 48, HIDDEN)]
    # other shapes are bound: the rows follow
    module.reshape([('data', (1, T))], [('softmax_label', (1, T))])
    assert [tuple(shapes[0]) for shapes in module._aux_shapes] == \
        [(T, HIDDEN)] * 4


def test_initializer_knows_the_expert_layers_states():
    symbol = models.get_symbol('lfm2_moe', seq_len=T, **SIZES)
    module = mx.mod.Module(symbol)
    module.bind(data_shapes=[('data', (N, T))],
                label_shapes=[('softmax_label', (N, T))])
    module.init_params(mx.init.Xavier())
    _, aux = module.get_params()
    assert sorted(aux) == sorted(symbol.list_auxiliary_states())
    for value in aux.values():
        assert not value.asnumpy().any()
