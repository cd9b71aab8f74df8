"""Nemotron-H on the CPU at a small size against its plain reference
(``mxnet_tpu/models/nemotron_h_reference.py``): the Mamba-2 operator against
the token-by-token recurrence, forward and every input's gradient, at several
chunk sizes and over more than one segment; ungated ``relu2`` experts in a
latent through every rung of ``SparseExperts``' ladder (``test_lfm2_moe.py``'s
cases, over the expert's form); the whole model's log-probabilities, loss
and every parameter's gradient; one ``Module.fit`` step with Adam; the shares
of all three kinds of block adding up to the uncut block; and what the step
needs of the new operator (mirror stages, inputs that keep their dtype,
scopes, counters, the initializer).

Sizes: hidden 64, blocks ``MEM*E``; Mamba-2 with 8 heads of 8 in 2 groups on a
state of 16, chunks of 16; 4 query heads over 2 key-value heads of 16; 32
experts of 40 in a latent of 24, 6 a token, a shared expert of 80; vocabulary
256; 2 x 48 = 96 tokens.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import instrument, models
from mxnet_tpu.executor import _mirror_stage_units
from mxnet_tpu.models import nemotron_h_reference as ref
from mxnet_tpu.ops import lm
from mxnet_tpu.ops.registry import get_op
from mxnet_tpu.parallel.train_step import make_fit_step

import test_lfm2_moe as lfm2
from test_kimi_linear import front_pads

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, T, HIDDEN, VOCAB = 2, 48, 64, 256
PATTERN = 'MEM*E'
SIZES = dict(vocab_size=VOCAB, hidden_size=HIDDEN,
             hybrid_override_pattern=PATTERN, mamba_num_heads=8,
             mamba_head_dim=8, ssm_state_size=16, n_groups=2, conv_kernel=4,
             chunk_size=16, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, n_routed_experts=32, num_experts_per_tok=6,
             moe_intermediate_size=40, moe_latent_size=24,
             n_shared_experts=1, moe_shared_expert_intermediate_size=80,
             routed_scaling_factor=5.0, experts_held=(0, 32))
SHAPES = {'data': (N, T), 'softmax_label': (N, T)}
ADAM = dict(learning_rate=5e-4, beta1=0.9, beta2=0.95, epsilon=1e-8, wd=0.1)

rel, draw = lfm2.rel, lfm2.draw


def make_params(symbol, seed, shapes=None):
    """Seeded arguments and auxiliary states of a model symbol."""
    rng = np.random.default_rng(seed)
    shapes = shapes or SHAPES
    arg_shapes, _, aux_shapes = symbol.infer_shape(**shapes)
    args, aux = {}, {}
    for name, shape in zip(symbol.list_arguments(), arg_shapes):
        if name in shapes:
            continue
        if name.endswith(('_gamma', '_ssm_D')):
            args[name] = 1.0 + draw(rng, shape, 0.1)
        elif name.endswith('_A_log'):
            args[name] = jnp.asarray(np.log(rng.uniform(1, 16, shape)),
                                     jnp.float32)
        elif name.endswith('_dt_bias'):
            args[name] = jnp.asarray(rng.uniform(-5, -1, shape), jnp.float32)
        elif name.endswith('_conv_bias'):
            args[name] = draw(rng, shape, 0.1)
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
        'mamba_num_heads', 'mamba_head_dim', 'ssm_state_size', 'n_groups',
        'num_attention_heads', 'num_key_value_heads', 'n_routed_experts',
        'num_experts_per_tok', 'experts_held', 'routed_scaling_factor')}
    config.update(pattern=PATTERN, norm_eps=1e-5, norm_topk_prob=True)
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


# -- the Mamba-2 operator against the recurrence -----------------------------

HEADS, SIZE, GROUPS, STATES, TAPS = 4, 8, 2, 6, 4


def mixer_inputs(seed, t, n=2):
    """The operator's nine inputs, seeded, in its order."""
    rng = np.random.default_rng(seed)
    d, mixed = HEADS * SIZE, HEADS * SIZE + 2 * GROUPS * STATES
    return [draw(rng, (n, t, d)), draw(rng, (n, t, mixed)),
            draw(rng, (n, t, HEADS)), draw(rng, (mixed, TAPS), 0.5),
            draw(rng, (mixed,), 0.2),
            jnp.asarray(np.log(rng.uniform(1, 16, HEADS)), jnp.float32),
            1.0 + draw(rng, (HEADS,), 0.1),
            jnp.asarray(rng.uniform(-4, 0, HEADS), jnp.float32),
            1.0 + draw(rng, (d,), 0.1)]


def mixer_attrs(chunk):
    return {'num_heads': HEADS, 'head_dim': SIZE, 'state_size': STATES,
            'num_groups': GROUPS, 'kernel': TAPS, 'chunk_size': chunk}


def plain_mixer(z, xbc, dt, kernel, bias, a_log, d, dt_bias, gamma):
    """The reference's mixer between its projections: ``ref.mamba`` with
    identities for ``W_in`` and ``W_out``."""
    width = z.shape[-1] + xbc.shape[-1] + dt.shape[-1]
    p = {'in_weight': jnp.eye(width), 'out_weight': jnp.eye(z.shape[-1]),
         'ssm_conv_weight': kernel, 'ssm_conv_bias': bias,
         'ssm_A_log': a_log, 'ssm_D': d, 'ssm_dt_bias': dt_bias,
         'ssm_norm_gamma': gamma}
    config = {'mamba_num_heads': HEADS, 'mamba_head_dim': SIZE,
              'n_groups': GROUPS, 'ssm_state_size': STATES, 'norm_eps': 1e-5}
    return ref.mamba(jnp.concatenate([z, xbc, dt], axis=-1), p, config)


def apply_mixer(chunk, inputs):
    op = get_op('Mamba2Mixer')
    outs, aux = op.apply(op.canon_attrs(mixer_attrs(chunk)),
                         list(inputs) + [jnp.zeros((2,))], True, None)
    return outs[0], aux['count']


# (tokens, chunk): whole chunks in one segment; 9 chunks, so three
# segments of three; a length that is no multiple of the chunk (padded); a
# chunk longer than the sequence; 16 chunks in two segments of eight; 9
# chunks in three segments of three, the last padded by 2 tokens, fewer
# than the three rows the convolution reads before a segment; sequences
# shorter than those three rows, in a chunk of three
MIXER_CASES = [(48, 16), (72, 8), (43, 16), (20, 64), (64, 4), (70, 8),
               (2, 64), (1, 16)]


@pytest.mark.parametrize('t, chunk', MIXER_CASES)
def test_the_chunked_mixer_is_the_recurrence_forward_and_backward(
        t, chunk, monkeypatch):
    """Against the recurrence, and against the same operator in one segment:
    the rows a segment reads before it (zeros before the first) and the
    cotangents it keeps for them, the last segment's padded rows."""
    inputs = mixer_inputs(t, t)
    cotangent = draw(np.random.default_rng(1), (2, t, HEADS * SIZE))
    which = tuple(range(len(inputs)))

    def program(*xs):
        out, count = apply_mixer(chunk, xs)
        return jnp.sum(out * cotangent), (out, count)

    def plain(*xs):
        with jax.default_matmul_precision('highest'):
            return jnp.sum(plain_mixer(*xs) * cotangent)

    def run():
        with jax.default_matmul_precision('highest'):
            return jax.grad(program, which, has_aux=True)(*inputs)
    with jax.default_matmul_precision('highest'):
        want = plain_mixer(*inputs)
    grads, (out, count) = run()
    grads_want = jax.grad(plain, which)(*inputs)
    monkeypatch.setattr(lm, 'SSM_SEGMENT', 64)           # every chunk in one
    grads_one, (out_one, _) = run()
    assert rel(out, want) < 2e-5
    assert rel(out, out_one) < 2e-5
    for name, got, wanted, one in zip(get_op('Mamba2Mixer').input_names({}),
                                      grads, grads_want, grads_one):
        assert bool(jnp.isfinite(got).all()), name
        assert rel(got, wanted) < 1e-4, name
        assert rel(got, one) < 1e-4, name
    size = min(chunk, t)
    np.testing.assert_array_equal(np.asarray(count),
                                  [2 * t, 2 * -(-t // size)])


@pytest.mark.parametrize('t, chunk', [(70, 8), (64, 4)])
def test_the_mixer_pads_nothing_in_front_of_its_arrays(t, chunk):
    """No array as long as the sequence is padded in front, forward or
    backward: a segment's three rows before it are cut apart from its rows
    (the convolution's own pads are a segment long); and ``_segments``
    refuses arrays whose length is no multiple of a segment, and segments
    shorter than the rows they read before them."""
    inputs = mixer_inputs(t, t)
    assert lm._segmenting(chunk, t, 1, lm.SSM_SEGMENT, 3)[1] * chunk < t
    assert front_pads(lambda *xs: apply_mixer(chunk, xs)[0], inputs, t) == []

    def segment(params, state, xs, first):
        return state, xs[0][:, 3:], jnp.float32(0)
    with pytest.raises(ValueError, match='no multiple'):
        lm._segments((segment, 24, 3, (1,)), (), (inputs[0],))
    with pytest.raises(ValueError, match='reads 3 before it'):
        lm._segments((segment, 2, 3, (1,)), (), (inputs[0],))


def test_the_mixer_runs_more_than_one_segment_where_the_chunks_allow():
    # 9 chunks go three to a segment, 16 go eight, 7 (a prime) one by one
    assert lm._segmenting(8, 72, 1, lm.SSM_SEGMENT, 3) == (8, 3, 0)
    assert lm._segmenting(4, 64, 1, lm.SSM_SEGMENT, 3) == (4, 8, 0)
    assert lm._segmenting(16, 112, 1, lm.SSM_SEGMENT, 3) == (16, 7, 0)
    assert lm._segmenting(16, 43, 1, lm.SSM_SEGMENT, 3) == (16, 3, 5)
    # the cell's: 64 chunks of 128 to a sequence of 8192, eight to a segment
    assert lm._segmenting(128, 8192, 1, lm.SSM_SEGMENT, 3) == (128, 8, 0)
    # a chunk is no shorter than the three rows a segment reads before it
    assert lm._segmenting(128, 2, 1, lm.SSM_SEGMENT, 3) == (3, 1, 1)
    assert lm._segmenting(1, 11, 1, lm.SSM_SEGMENT, 3) == (3, 4, 1)
    # Kimi Delta Attention's are what they were
    assert lm._segmenting(64, 8192, lm.KDA_SUB, lm.KDA_SEGMENT, 3) == \
        (64, 16, 0)
    assert lm._segmenting(16, 40, lm.KDA_SUB, lm.KDA_SEGMENT, 3) == \
        (16, 3, 8)


def test_the_mixer_in_bf16_follows_the_recurrence():
    inputs = mixer_inputs(5, 48)
    keep = get_op('Mamba2Mixer').keep_dtype
    names = get_op('Mamba2Mixer').input_names({})
    cast = [x if n in keep else x.astype(jnp.bfloat16)
            for n, x in zip(names, inputs)]
    with jax.default_matmul_precision('highest'):
        want = plain_mixer(*(x.astype(jnp.float32) for x in cast))
    out, _ = apply_mixer(16, cast)
    assert out.dtype == jnp.bfloat16
    assert rel(out, want) < 4e-2


def test_the_chunked_scan_carries_a_state_it_is_given():
    """Two halves, the second from the state the first left, are the whole."""
    rng = np.random.default_rng(2)
    n, t, g = 2, 32, 2
    x = draw(rng, (n, t, HEADS, SIZE))
    b, c = draw(rng, (n, t, g, STATES)), draw(rng, (n, t, g, STATES))
    dt = jnp.asarray(rng.uniform(0.01, 1.0, (n, t, HEADS)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 8, HEADS), jnp.float32)
    zero = jnp.zeros((n, HEADS, SIZE, STATES))
    with jax.default_matmul_precision('highest'):
        state, whole = lm.ssd_chunked(x, b, c, dt, a, zero, 4)
        half, first = lm.ssd_chunked(x[:, :16], b[:, :16], c[:, :16],
                                     dt[:, :16], a, zero, 2)
        after, second = lm.ssd_chunked(x[:, 16:], b[:, 16:], c[:, 16:],
                                       dt[:, 16:], a, half, 2)
    assert rel(jnp.concatenate([first, second], axis=1), whole) < 1e-5
    assert rel(after, state) < 1e-5


def test_mixer_symbol_infers_its_arrays_and_round_trips():
    symbol = mx.sym.Mamba2Mixer(
        z=mx.sym.Variable('z'), name='ssm', **mixer_attrs(16))
    mixed = HEADS * SIZE + 2 * GROUPS * STATES
    arg_shapes, out_shapes, aux_shapes = symbol.infer_shape(
        z=(2, 48, HEADS * SIZE))
    found = dict(zip(symbol.list_arguments(), arg_shapes))
    assert found == {
        'z': (2, 48, 32), 'ssm_xBC': (2, 48, mixed), 'ssm_dt': (2, 48, HEADS),
        'ssm_conv_weight': (mixed, TAPS), 'ssm_conv_bias': (mixed,),
        'ssm_A_log': (HEADS,), 'ssm_D': (HEADS,), 'ssm_dt_bias': (HEADS,),
        'ssm_norm_gamma': (32,)}
    assert symbol.list_auxiliary_states() == ['ssm_count']
    assert [tuple(s) for s in aux_shapes] == [(2,)]
    assert tuple(out_shapes[0]) == (2, 48, 32)
    text = symbol.tojson()
    assert mx.sym.load_json(text).tojson() == text
    with pytest.raises(Exception):
        mx.sym.Mamba2Mixer(z=mx.sym.Variable('z'), name='ssm', **dict(
            mixer_attrs(16), num_heads=5)).infer_shape(z=(2, 48, 32))


# -- ungated experts in a latent: ``test_lfm2_moe.py``'s cases, by form ------

LATENT, WIDTH = 24, 40


def relu2_in_a_latent(case):
    """A case of ``test_lfm2_moe.py``'s ``SparseExperts`` cases with the
    expert's form changed: the same tokens, router, bias and loads, experts
    of two matrices with ``relu2`` between them on the rows of a latent."""
    def changed(rng):
        name, attrs, inputs, aux, _ = case(rng)
        z, router = inputs[:2]
        held = attrs['experts_held']
        rows = draw(rng, (z.shape[0], LATENT))
        w1 = draw(rng, (held[1], LATENT, WIDTH), 0.2)
        w2 = draw(rng, (held[1], WIDTH, LATENT), 0.16)
        config = dict(
            n_routed_experts=attrs['num_experts'], experts_held=held,
            num_experts_per_tok=attrs['experts_per_tok'],
            routed_scaling_factor=5.0, norm_topk_prob=True)
        attrs = dict(attrs, expert_hidden=WIDTH, expert_form='relu2',
                     latent_input=True, topk_eps=1e-20,
                     routed_scaling_factor=5.0)
        return name, attrs, [z, rows, router, w1, w2], aux, \
            lambda z, rows, r, a, b: ref.expert_layer(
                z, rows, r, aux[0], a, b, config)[0]
    changed.__name__ = case.__name__
    return changed


def six_of_thirty_two(rng):
    """22 of 512's ratio at a small size: 6 of 32 a token, 4 held; gated, as
    ``test_lfm2_moe.py`` has it, with that file's reference told 6 a token."""
    name, attrs, inputs, aux, _ = lfm2.case_experts(rng, (8, 4), experts=32)
    config = lfm2.reference_config(experts_held=(8, 4), num_experts=32,
                                   num_experts_per_tok=6)
    return name, dict(attrs, experts_per_tok=6), inputs, aux, \
        lambda z, r, a, b, c: lfm2.ref.expert_layer(
            z, r, aux[0], a, b, c, config)[0]


six_of_thirty_two.__name__ = 'case_experts_six_of_thirty_two'
EXPERT_CASES = [c for c in lfm2.CASES if c.__name__.startswith('case_experts')]
# every case in the ungated form; the gated default on ``test_lfm2_moe.py``'s
# cases is that file's own test, which this PR leaves as it was, so here it
# takes the one case that file does not have
BY_FORM = [(relu2_in_a_latent(case), 'relu2')
           for case in EXPERT_CASES + [six_of_thirty_two]] + \
    [(six_of_thirty_two, 'swiglu')]


@pytest.mark.parametrize('dtype', lfm2.DTYPES, ids=['float32', 'bfloat16'])
@pytest.mark.parametrize('case, form', BY_FORM,
                         ids=['%s-%s' % (c.__name__[5:], f)
                              for c, f in BY_FORM])
def test_experts_of_either_form_agree_with_a_masked_loop(case, form, dtype):
    rng = np.random.default_rng(11)
    name, attrs, inputs, aux, reference = case(rng)
    op = get_op(name)
    in_names = op.input_names(op.canon_attrs(attrs))
    assert ('w3_weight' in in_names) == (form == 'swiglu')
    cast = [x if n in op.keep_dtype else x.astype(dtype)
            for n, x in zip(in_names, inputs)]
    rounded = [x.astype(jnp.float32) for x in cast]
    with jax.default_matmul_precision('highest'):
        want = reference(*rounded)
    cotangent = draw(rng, want.shape)

    def program(*xs):
        out = lfm2.apply_op(name, attrs, xs, aux)[0][0]
        return jnp.sum(out.astype(jnp.float32) * cotangent), out

    def plain(*xs):
        with jax.default_matmul_precision('highest'):
            return jnp.sum(reference(*xs) * cotangent)

    which = tuple(range(len(inputs)))
    grads, out = jax.grad(program, which, has_aux=True)(*cast)
    grads_want = jax.grad(plain, which)(*rounded)
    limit = lfm2.TOLERANCE[dtype]
    assert out.dtype == dtype and out.shape == want.shape
    assert rel(out, want) < limit
    for got, wanted in zip(grads, grads_want):
        assert bool(jnp.isfinite(got).all())
        assert rel(got, wanted) < 2 * limit


@pytest.mark.parametrize('which', sorted(lfm2.ON_THE_LADDER))
def test_an_ungated_step_takes_the_smallest_rung_that_holds_it(which):
    """Every rung of the ladder is taken by the ungated form too."""
    loads, rung = lfm2.ON_THE_LADDER[which]
    rng = np.random.default_rng(11)
    name, attrs, inputs, aux, _ = relu2_in_a_latent(
        lfm2.LADDER_CASES[which])(rng)
    _, states = lfm2.apply_op(name, attrs, inputs, aux)
    np.testing.assert_array_equal(np.asarray(states['expert_load']), loads)
    count = np.asarray(states['expert_count'])
    assert count[2] == 0 and count[3] == (rung == len(lfm2.LADDER) - 1)
    rooms, _, align = lm._room(lfm2.N * lfm2.T * 4, 3, 32)
    assert rooms == lfm2.LADDER
    assert lm._rung(rooms, int(lm._aligned(np.asarray(loads), align).sum())) \
        == rung


def test_the_ladder_at_twenty_two_of_five_hundred_and_twelve():
    """The cell's layer: 16384 tokens x 22 over 512 experts, 8 held: 5632
    rows arrive, the rungs are two and four such shares and room for all,
    and eight experts of 704 rows from multiples of 512 fit the first."""
    rooms, four, align = lm._room(16384 * 22, 8, 512)
    assert (rooms, four, align) == ((11264, 22528, 364544), 22528, 512)
    assert int(lm._aligned(np.full(8, 704), align).sum()) == 8192
    assert lm._rung(rooms, 8192) == 0


def test_experts_symbol_names_its_inputs_by_form_and_round_trips():
    for extra, names in (
            ({}, ['data', 'moe_router_weight', 'moe_w1_weight',
                  'moe_w3_weight', 'moe_w2_weight']),
            ({'expert_form': 'relu2'},
             ['data', 'moe_router_weight', 'moe_w1_weight', 'moe_w2_weight']),
            ({'expert_form': 'relu2', 'latent_input': True},
             ['data', 'moe_latent', 'moe_router_weight', 'moe_w1_weight',
              'moe_w2_weight'])):
        symbol = mx.sym.SparseExperts(
            mx.sym.Variable('data'), num_experts=32, experts_held=(8, 4),
            experts_per_tok=6, expert_hidden=WIDTH, name='moe', **extra)
        assert symbol.list_arguments() == names
        given = {'data': (96, HIDDEN)}
        if 'moe_latent' in names:
            given['moe_latent'] = (96, LATENT)
        arg_shapes, out_shapes, _ = symbol.infer_shape(**given)
        found = dict(zip(names, (tuple(s) for s in arg_shapes)))
        rows = LATENT if 'moe_latent' in names else HIDDEN
        assert found['moe_router_weight'] == (32, HIDDEN)
        assert found['moe_w1_weight'] == (4, rows, WIDTH)
        assert found['moe_w2_weight'] == (4, WIDTH, rows)
        assert tuple(out_shapes[0]) == (96, rows)
        text = symbol.tojson()
        assert mx.sym.load_json(text).tojson() == text
    with pytest.raises(ValueError):
        mx.sym.SparseExperts(mx.sym.Variable('data'), num_experts=32,
                             experts_held=(8, 4), expert_form='gelu',
                             name='moe')


# -- the whole model ---------------------------------------------------------

@pytest.fixture(scope='module')
def model():
    symbol = models.get_symbol('nemotron_h', seq_len=T, **SIZES)
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
    assert 'nemotron_h' in models.list_models()
    names = set(symbol.list_arguments()) - set(SHAPES)
    names |= {n for n in symbol.list_auxiliary_states()
              if n.endswith('_expert_bias')}
    assert names == set(ref.param_names(reference_config()))
    assert {'embed_weight', 'lm_head_weight'} <= names
    # the ungated experts have no third matrix, the shared expert two
    assert not [n for n in names if 'w3' in n]


def test_model_float32_agrees_tightly(model):
    prob, aux, grads = run_step(model['symbol'], model['args'], model['aux'],
                                model['tokens'], model['labels'], jnp.float32)
    assert np.abs(np.log(prob) - model['log_prob']).max() < 2e-4
    picked = np.log(prob)[np.arange(N * T), model['labels'].reshape(-1)]
    assert abs(-picked.sum() - model['loss']) < 1e-3 * model['loss']
    assert set(grads) == set(model['grads'])
    for name, want in model['grads'].items():
        assert rel(grads[name], want) < 2e-4, name
    assert sorted(model['load']) == [1, 4]
    for layer, load in model['load'].items():
        np.testing.assert_array_equal(
            np.asarray(aux['l%d_moe_expert_load' % layer]), np.asarray(load))
        np.testing.assert_array_equal(
            np.asarray(aux['l%d_moe_expert_count' % layer]),
            [N * T * 6, N * T * 6, 0, 0])
    for layer in (0, 2):
        np.testing.assert_array_equal(
            np.asarray(aux['l%d_ssm_count' % layer]), [N * T, N * 3])


def test_model_bf16_follows_the_reference(model):
    from benchmark.drivers import fit_nemotron_h as driver
    prob, aux, grads = run_step(model['symbol'], model['args'], model['aux'],
                                model['tokens'], model['labels'],
                                jnp.bfloat16)
    readings = driver.forward_readings(prob, np.exp(model['log_prob']))
    print('bf16 program', readings)
    # at these widths (hidden 64, 6 of 32 near-equal scores) bf16 tips a
    # routing for more tokens than at the published ones: the driver's
    # limits are held on the chip, the median here
    assert readings['token_error_median'] < 0.1
    assert readings['row_agreement'] > 0.9
    picked = np.log(prob)[np.arange(N * T), model['labels'].reshape(-1)]
    assert abs(-picked.sum() - model['loss']) < 0.02 * model['loss']
    for name in model['grads']:
        assert grads[name].dtype == jnp.float32
        assert np.isfinite(np.asarray(grads[name])).all(), name
    for layer in model['load']:
        assert float(aux['l%d_moe_expert_count' % layer][2]) == 0


# each the plain reference with one thing changed
WRONG = ('a_rotary_embedding', 'gated_experts', 'one_expert_too_few',
         'the_shared_expert_left_out', 'no_skip_from_x')


def wrong_model(which, model, monkeypatch):
    """The reference's log-probabilities with one thing changed."""
    config = reference_config()
    params = dict(with_bias(model['args'], model['aux']))
    if which == 'a_rotary_embedding':
        plain = ref.causal_attention
        monkeypatch.setattr(
            ref, 'causal_attention', lambda q, k, v, scale: plain(
                lfm2.ref.rotary(q, 1e4), lfm2.ref.rotary(k, 1e4), v, scale))
    elif which == 'gated_experts':
        monkeypatch.setattr(ref, 'relu2', lambda x: x * jax.nn.sigmoid(x))
    elif which == 'one_expert_too_few':
        config['num_experts_per_tok'] = 5
    elif which == 'the_shared_expert_left_out':
        monkeypatch.setattr(ref, 'shared_expert',
                            lambda u, w1, w2: jnp.zeros_like(u))
    elif which == 'no_skip_from_x':
        params = {k: (jnp.zeros_like(v) if k.endswith('_ssm_D') else v)
                  for k, v in params.items()}
    return np.asarray(ref.forward(params, model['tokens'], config)[0],
                      np.float64)


@pytest.mark.parametrize('which', WRONG)
def test_a_wrong_model_is_farther_from_the_program_than_the_limits(
        model, which, monkeypatch):
    from benchmark.drivers import fit_nemotron_h as driver
    prob, _, _ = run_step(model['symbol'], model['args'], model['aux'],
                          model['tokens'], model['labels'], jnp.float32)
    wrong = wrong_model(which, model, monkeypatch)
    readings = driver.forward_readings(prob, np.exp(wrong))
    print(which, readings)
    assert driver.broken(readings), which


ADAM_STEP = dict(ADAM, rescale_grad=1.0 / N)


def one_fit_step(model, dtype):
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
    # the cell's optimizer: wd added to the gradient of what MXNet decays
    # (not A_log, D, dt_bias, the convolution's bias), Module's default
    # rescale_grad of one over the batch's rows
    got, aux, _ = one_fit_step(model, None)
    zeros = {k: jnp.zeros_like(v) for k, v in model['args'].items()}
    want = ref.adam_step(model['args'], model['grads'], zeros, zeros, 1,
                         ADAM_STEP)
    for name, (param, _, _) in want.items():
        moved = np.asarray(param) - np.asarray(model['args'][name])
        assert rel(got[name] - np.asarray(model['args'][name]),
                   moved) < 0.1, name
    for name, value in model['aux'].items():
        if name.endswith('_expert_bias'):       # left alone by the step
            np.testing.assert_array_equal(aux[name].asnumpy(),
                                          np.asarray(value))
    assert not ref.decayed('l0_ssm_A_log') and not ref.decayed('l0_ssm_D')
    assert not ref.decayed('l0_ssm_conv_bias')
    assert ref.decayed('l0_ssm_conv_weight') and ref.decayed('l0_norm_gamma')


def test_first_update_is_held_array_by_array_and_wrong_ones_refused(model):
    from benchmark.drivers import fit_nemotron_h as driver
    got, _, state = one_fit_step(model, None)
    readings, leaves = driver.update_readings(
        ref, ADAM_STEP, host(model['args']), host(model['grads']), got, state)
    print('float32 step', readings)
    assert driver.broken(readings) == []
    assert set(leaves) == set(model['args'])
    # an array the optimizer never moved reads 1 and is refused
    stuck = dict(got, l0_ssm_D=np.asarray(model['args']['l0_ssm_D']))
    readings, _ = driver.update_readings(
        ref, ADAM_STEP, host(model['args']), host(model['grads']), stuck,
        state)
    assert driver.broken(readings) == ['update_error_worst']
    # a gradient over half the batch is refused
    halved = {k: (v[0] * 0.5,) + tuple(v[1:]) for k, v in state.items()}
    readings, _ = driver.update_readings(
        ref, ADAM_STEP, host(model['args']), host(model['grads']), got,
        halved)
    assert 'gradient_error_median' in driver.broken(readings)


def test_the_two_copies_of_the_reference_are_the_same_file():
    marker = '# -- everything below this line is the same in both copies'
    bodies = []
    for path in ('mxnet_tpu/models/nemotron_h_reference.py',
                 'benchmark/reference_nemotron_h.py'):
        with open(os.path.join(ROOT, path)) as f:
            head, _, body = f.read().partition(marker)
        assert body and head.lstrip().startswith('"""'), path
        bodies.append(body)
    assert bodies[0] == bodies[1]


# -- the shares add up --------------------------------------------------------

def mixer_share(p, rank, ranks, config):
    """Rank ``rank`` of ``ranks``'s arrays of an uncut ``M`` block: the rows
    of ``W_in`` for its heads' ``z``, ``x`` and ``dt`` and its groups' ``B``
    and ``C``, the same channels of the convolution and the norm, its heads'
    ``A_log``, ``D`` and ``dt_bias``, and the columns of ``W_out`` that read
    its heads."""
    heads, size = config['mamba_num_heads'], config['mamba_head_dim']
    groups, states = config['n_groups'], config['ssm_state_size']
    d, gs = heads * size, groups * states

    def part(first, width):
        # this rank's share of ``width`` consecutive rows from ``first``
        return np.arange(first + rank * width // ranks,
                         first + (rank + 1) * width // ranks)
    z, x = part(0, d), part(d, d)
    b, c = part(2 * d, gs), part(2 * d + gs, gs)
    dt = part(2 * d + 2 * gs, heads)
    mixed = np.concatenate([x, b, c]) - d
    mine = part(0, heads)
    return dict(
        p, in_weight=p['in_weight'][np.concatenate([z, x, b, c, dt])],
        ssm_conv_weight=p['ssm_conv_weight'][mixed],
        ssm_conv_bias=p['ssm_conv_bias'][mixed],
        ssm_A_log=p['ssm_A_log'][mine], ssm_D=p['ssm_D'][mine],
        ssm_dt_bias=p['ssm_dt_bias'][mine],
        ssm_norm_gamma=p['ssm_norm_gamma'][z],
        out_weight=p['out_weight'][:, z])


def attention_share(p, rank, ranks, config):
    """Rank ``rank``'s query heads and the key-value heads they use."""
    heads, kv = config['num_attention_heads'], config['num_key_value_heads']
    size = p['q_weight'].shape[0] // heads
    q = np.arange(rank * heads // ranks, (rank + 1) * heads // ranks)
    used = sorted({h // (heads // kv) for h in q})

    def rows(which):
        return np.concatenate([np.arange(h * size, (h + 1) * size)
                               for h in which])
    return dict(p, q_weight=p['q_weight'][rows(q)],
                k_weight=p['k_weight'][rows(used)],
                v_weight=p['v_weight'][rows(used)],
                o_weight=p['o_weight'][:, rows(q)])


def block_arrays(model, index, kind):
    prefix = 'l%d_' % index
    everything = with_bias(model['args'], model['aux'])
    return {k[len(prefix):]: everything[k]
            for k in ref.layer_param_names(index, kind)}


@pytest.mark.parametrize('ranks', [2, 4])
def test_the_shares_of_a_mamba_block_add_up_to_the_uncut_block(model, ranks):
    """``mixers_held``: each of ``ranks`` shares computes its heads' part of
    ``W_out``'s sum; together they are the uncut mixer.  With 2 groups, two
    ranks hold a group each; four are refused (a group is not divided)."""
    config = reference_config()
    p = block_arrays(model, 0, 'M')
    u = draw(np.random.default_rng(7), (N, T, HIDDEN))
    if ranks > config['n_groups']:
        with pytest.raises(ValueError):
            models.get_symbol('nemotron_h', seq_len=T,
                              **dict(SIZES, mixers_held=(0, ranks)))
        return
    with jax.default_matmul_precision('highest'):
        whole = ref.mamba(u, p, config)
        held = dict(config, mamba_num_heads=config['mamba_num_heads'] // ranks,
                    n_groups=config['n_groups'] // ranks)
        parts = [ref.mamba(u, mixer_share(p, r, ranks, config), held)
                 for r in range(ranks)]
    assert rel(sum(parts), whole) < 1e-5
    assert rel(parts[0], whole) > 0.3       # a share alone is not the block
    # and the program, built for a share, computes that share
    symbol = models.get_symbol('nemotron_h', seq_len=T, **dict(
        SIZES, hybrid_override_pattern='M', mixers_held=(1, ranks)))
    mine = mixer_share(p, 1, ranks, config)
    shapes = dict(zip(symbol.list_arguments(),
                      symbol.infer_shape(**SHAPES)[0]))
    for name, value in mine.items():
        assert tuple(shapes['l0_' + name]) == value.shape, name


@pytest.mark.parametrize('ranks', [1, 2, 4])
def test_the_shares_of_an_attention_block_add_up_to_the_uncut_block(model,
                                                                    ranks):
    """4 query heads over 2 key-value heads: two ranks hold a key-value head
    each, four hold one of two alike, as the cell's eight hold 2."""
    config = reference_config()
    p = block_arrays(model, 3, '*')
    u = draw(np.random.default_rng(8), (N, T, HIDDEN))
    held = dict(config, num_attention_heads=4 // ranks,
                num_key_value_heads=max(1, 2 // ranks))
    with jax.default_matmul_precision('highest'):
        whole = ref.attention(u, p, config)
        parts = [ref.attention(u, attention_share(p, r, ranks, config), held)
                 for r in range(ranks)]
    assert rel(sum(parts), whole) < 1e-5
    symbol = models.get_symbol('nemotron_h', seq_len=T, **dict(
        SIZES, hybrid_override_pattern='*', n_groups=4,
        mixers_held=(ranks - 1, ranks)))
    shapes = dict(zip(symbol.list_arguments(),
                      symbol.infer_shape(**SHAPES)[0]))
    for name, value in attention_share(p, ranks - 1, ranks, config).items():
        assert tuple(shapes['l0_' + name]) == value.shape, name


def test_eight_shares_of_the_experts_with_the_shared_one_once_add_up(model):
    """``experts_held``: over the 8 shares of 4 of 32 experts, the routed
    parts, each through the latent's up-projection (which is linear), with
    the shared expert counted once, are the uncut reference's block."""
    config = reference_config()
    p = block_arrays(model, 1, 'E')
    u = draw(np.random.default_rng(9), (N * T, HIDDEN))
    with jax.default_matmul_precision('highest'):
        whole, load = ref.latent_moe(u, p, config)
        shared = ref.shared_expert(u, p['shared_w1_weight'],
                                   p['shared_w2_weight'])
        total, loads = shared, []
        for first in range(0, 32, 4):
            mine = dict(p, **{k: p[k][first:first + 4] for k in (
                'experts_w1_weight', 'experts_w2_weight')})
            part, held = ref.latent_moe(u, mine, dict(config,
                                                      experts_held=(first, 4)))
            total = total + (part - shared)
            loads.append(np.asarray(held))
            # the program, told the same share, gives the same routed part
            name, attrs = 'SparseExperts', dict(
                num_experts=32, experts_held=(first, 4), experts_per_tok=6,
                expert_hidden=40, routed_scaling_factor=5.0,
                expert_form='relu2', latent_input=True, topk_eps=1e-20)
            routed = lfm2.apply_op(
                name, attrs,
                [u, u @ p['down_weight'].T, p['router_weight'],
                 mine['experts_w1_weight'], mine['experts_w2_weight']],
                [p['moe_expert_bias'], jnp.zeros(4), jnp.zeros(4)])[0][0]
            assert rel(routed @ p['up_weight'].T, part - shared) < 1e-4
    assert rel(total, whole) < 1e-5
    np.testing.assert_array_equal(np.concatenate(loads), np.asarray(load))
    assert int(np.asarray(load).sum()) == N * T * 6


def test_the_builder_refuses_what_it_does_not_build():
    for change in ({'use_conv_bias': False}, {'mlp_hidden_act': 'silu'},
                   {'attention_bias': True}, {'n_group': 2},
                   {'tie_word_embeddings': True},
                   {'num_nextn_predict_layers': 1},
                   {'hybrid_override_pattern': 'ME-'},
                   {'num_hidden_layers': 4}, {'mixers_held': (2, 2)},
                   {'mixers_held': (0, 3)}):
        with pytest.raises(ValueError):
            models.get_symbol('nemotron_h', seq_len=T,
                              **dict(SIZES, **change))
    # the published pattern: 88 blocks, 40 : 40 : 8
    pattern = models.nemotron_h.PUBLISHED_PATTERN
    assert (len(pattern), pattern.count('M'), pattern.count('E'),
            pattern.count('*')) == (88, 40, 40, 8)
    assert pattern[27:38] == 'MEMEMEMEM*E'


# -- what the step needs of it -----------------------------------------------

def test_every_sub_block_is_a_mirror_stage_and_the_gradient_is_left_alone(
        model):
    symbol = model['symbol']
    units = _mirror_stage_units(symbol.topo_nodes(), symbol._outputs)
    staged = [[n.op for _, n in members] for members, taken, _ in units
              if taken is not None]
    assert len(staged) == len(PATTERN)
    assert sum('Mamba2Mixer' in ops for ops in staged) == 2
    assert sum('FlashAttention' in ops for ops in staged) == 1
    # the latent's two projections and the shared expert's two lie in the
    # experts' stage
    assert all(ops.count('FullyConnected') == 4 for ops in staged
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


def test_the_operators_scopes_reach_the_lowered_step(model):
    step = make_fit_step(model['symbol'], GradsOut(), data_names=('data',),
                         compute_dtype=jnp.bfloat16, donate=False, _raw=True)
    batch = {'data': jnp.zeros((N, T), jnp.float32),
             'softmax_label': jnp.zeros((N, T), jnp.float32)}
    lowered = jax.jit(step).lower(
        dict(model['args']), {}, dict(model['aux']), {}, batch,
        jnp.float32(0), jax.random.PRNGKey(0))
    text = lowered.as_text(debug_info=True)
    for scope in ('Mamba2Mixer/l0_ssm/scan', 'FullyConnected/l1_down',
                  'FullyConnected/l1_up', 'SparseExperts/l1_moe',
                  'FlashAttention/l3_att'):
        assert scope in text, scope
    # the convolution, the gates and the output's gate run a segment at a
    # time inside the outer scan: their scopes lie under ``scan``
    for nested in ('conv', 'gates', 'out_gate'):
        assert '/%s/' % nested in text, nested
    # A_log, D and dt_bias (8,) are cast nowhere
    jaxpr = str(jax.make_jaxpr(step)(
        dict(model['args']), {}, dict(model['aux']), {}, batch,
        jnp.float32(0), jax.random.PRNGKey(0)))
    assert 'bf16[8]' not in jaxpr


def test_device_counters_reach_the_registry_only_at_a_drain(model):
    was = instrument.metrics_enabled()
    instrument.set_metrics(True)
    names = ('ssm.tokens', 'ssm.chunks', 'moe.assignments',
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
                instrument.counter_value('ssm.tokens')))
        after = instrument.metrics_snapshot()['counters']
        moved = {k: after.get(k, 0) - before.get(k, 0) for k in names}
        # three steps of two Mamba-2 blocks, 3 chunks of 16 to a sequence
        # of 48; two expert blocks at 6 a token
        assert moved['ssm.tokens'] == 3 * 2 * N * T
        assert moved['ssm.chunks'] == 3 * 2 * N * 3
        assert moved['moe.assignments'] == 3 * 2 * N * T * 6
        assert moved['moe.tokens_dropped'] == 0
        # nothing was written between the drains: no callback saw a count
        assert seen == [before.get('ssm.tokens', 0)] * 3
    finally:
        instrument.set_metrics(was)


def test_initializer_knows_the_new_operators_arrays():
    symbol = models.get_symbol('nemotron_h', seq_len=T, **SIZES)
    module = mx.mod.Module(symbol)
    module.bind(data_shapes=[('data', (N, T))],
                label_shapes=[('softmax_label', (N, T))])
    module.init_params(mx.init.Xavier())
    args, aux = module.get_params()
    assert sorted(aux) == sorted(symbol.list_auxiliary_states())
    for value in aux.values():
        assert not value.asnumpy().any()
    # no normal draw over the logarithm of a rate, a step's bias or the
    # skip: a rate of one, a bias of zero, a skip of one
    assert not args['l0_ssm_A_log'].asnumpy().any()
    assert not args['l0_ssm_dt_bias'].asnumpy().any()
    assert not args['l0_ssm_conv_bias'].asnumpy().any()
    assert (args['l0_ssm_D'].asnumpy() == 1).all()
    assert (args['l0_ssm_norm_gamma'].asnumpy() == 1).all()
    assert args['l0_ssm_conv_weight'].asnumpy().any()
    assert args['l1_experts_w1_weight'].asnumpy().any()
