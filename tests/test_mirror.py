"""MXNET_BACKWARD_DO_MIRROR — gradient rematerialization
(reference graph_executor.cc:199-216 mirror pass; env_var.md:56-60).
TPU mapping: jax.checkpoint around the differentiated forward.  And the
``__mirror_stage__`` blocks of a graph: what a stage keeps of what its ops
mark (``ops.registry.keep``), and that a stage that marks nothing is a
plain ``jax.checkpoint``."""
import os

import numpy as np
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu.parallel.train_step import (make_train_step,
                                           make_sgd_momentum,
                                           sgd_momentum_init)


def _run_steps(monkeypatch, mirror, policy='nothing', steps=3):
    if mirror:
        monkeypatch.setenv('MXNET_BACKWARD_DO_MIRROR', '1')
        monkeypatch.setenv('MXNET_BACKWARD_MIRROR_POLICY', policy)
    else:
        monkeypatch.delenv('MXNET_BACKWARD_DO_MIRROR', raising=False)
    import jax
    sym = models.get_symbol('lenet', num_classes=10)
    dshape = (8, 1, 28, 28)
    arg_shapes, _, _ = sym.infer_shape(data=dshape)
    rng = np.random.RandomState(0)
    params = {n: jnp.asarray(rng.normal(0, 0.05, s).astype(np.float32))
              for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in ('data', 'softmax_label')}
    batch = {'data': jnp.asarray(rng.rand(*dshape).astype(np.float32)),
             'softmax_label': jnp.asarray(
                 rng.randint(0, 10, 8).astype(np.float32))}
    opt = make_sgd_momentum(lr=0.1, momentum=0.9, wd=0.0, rescale_grad=1.0)
    state = sgd_momentum_init(params)
    step = make_train_step(sym, opt, ('data', 'softmax_label'),
                           donate=False)
    key = jax.random.PRNGKey(0)
    aux = {}
    for _ in range(steps):
        outs, params, aux, state = step(params, aux, state, batch, key)
    return {k: np.asarray(v) for k, v in params.items()}


def test_mirror_matches_unmirrored(monkeypatch):
    base = _run_steps(monkeypatch, mirror=False)
    for policy in ('nothing', 'dots'):
        # 'nothing' checkpoints the whole forward: XLA recomputes the
        # exact same fused program and the parameters stay bitwise
        # identical.  'dots' saves only the matmul/conv outputs, so the
        # recomputed elementwise/pool chains land in DIFFERENT fusion
        # boundaries than the plain forward — few-ulp reassociation
        # noise (measured max |delta| ~6e-6 on CPU XLA) that three
        # momentum steps amplify past the bitwise-era atol=1e-6.  The
        # loosened tolerance still fails on any real gradient bug
        # (wrong remat policy diverges at the 1e-2 level by step 3).
        atol = 1e-6 if policy == 'nothing' else 5e-5
        mirrored = _run_steps(monkeypatch, mirror=True, policy=policy)
        for k in base:
            assert np.allclose(base[k], mirrored[k], rtol=1e-4,
                               atol=atol), (policy, k)


def test_mirror_recomputes_forward(monkeypatch):
    """Under full remat the compiled program re-runs forward work during
    backward: XLA-counted FLOPs must rise vs the unmirrored step.  (CPU
    XLA's memory_analysis reports temp sizes that do not reflect remat,
    so FLOPs — not bytes — is the portable signal that the mirror pass
    engaged; the HBM saving itself is exercised on TPU runs.)"""
    import jax

    def step_flops(mirror):
        if mirror:
            monkeypatch.setenv('MXNET_BACKWARD_DO_MIRROR', '1')
            monkeypatch.setenv('MXNET_BACKWARD_MIRROR_POLICY', 'nothing')
        else:
            monkeypatch.delenv('MXNET_BACKWARD_DO_MIRROR', raising=False)
        sym = models.get_symbol('resnet-18', num_classes=10,
                                image_shape=(3, 64, 64))
        dshape = (64, 3, 64, 64)
        arg_shapes, _, aux_shapes = sym.infer_shape(data=dshape)
        rng = np.random.RandomState(0)
        params = {n: jnp.asarray(rng.normal(0, 0.05, s).astype(np.float32))
                  for n, s in zip(sym.list_arguments(), arg_shapes)
                  if n not in ('data', 'softmax_label')}
        aux = {n: (jnp.ones(s, jnp.float32) if 'var' in n
                   else jnp.zeros(s, jnp.float32))
               for n, s in zip(sym.list_auxiliary_states(), aux_shapes)}
        batch = {'data': jnp.asarray(rng.rand(*dshape).astype(np.float32)),
                 'softmax_label': jnp.asarray(
                     rng.randint(0, 10, 64).astype(np.float32))}
        opt = make_sgd_momentum(lr=0.1, momentum=0.9, wd=0.0,
                                rescale_grad=1.0)
        state = sgd_momentum_init(params)
        step = make_train_step(sym, opt, ('data', 'softmax_label'),
                               donate=False)
        lowered = step.lower(params, aux, state, batch,
                             jax.random.PRNGKey(0))
        ca = lowered.compile().cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        return float(ca.get('flops', 0.0)) if ca else None

    plain = step_flops(False)
    remat = step_flops(True)
    if not plain or not remat:
        pytest.skip('cost_analysis unavailable on this backend')
    assert remat > plain * 1.1, (remat, plain)


def test_dots_policy_saves_convs(monkeypatch):
    """'dots' must NOT recompute convolutions: its step FLOPs stay well
    below the 'nothing' policy's on a conv net."""
    import jax
    from mxnet_tpu.parallel.train_step import (make_train_step,
                                               make_sgd_momentum,
                                               sgd_momentum_init)

    def step_flops(policy):
        monkeypatch.setenv('MXNET_BACKWARD_DO_MIRROR', '1')
        monkeypatch.setenv('MXNET_BACKWARD_MIRROR_POLICY', policy)
        sym = models.get_symbol('lenet', num_classes=10)
        dshape = (32, 1, 28, 28)
        arg_shapes, _, _ = sym.infer_shape(data=dshape)
        rng = np.random.RandomState(0)
        params = {n: jnp.asarray(
                      rng.normal(0, 0.05, s).astype(np.float32))
                  for n, s in zip(sym.list_arguments(), arg_shapes)
                  if n not in ('data', 'softmax_label')}
        batch = {'data': jnp.asarray(
                     rng.rand(*dshape).astype(np.float32)),
                 'softmax_label': jnp.asarray(
                     rng.randint(0, 10, 32).astype(np.float32))}
        opt = make_sgd_momentum(lr=0.1, momentum=0.9, wd=0.0,
                                rescale_grad=1.0)
        step = make_train_step(sym, opt, ('data', 'softmax_label'),
                               donate=False)
        ca = step.lower(params, {}, sgd_momentum_init(params), batch,
                        jax.random.PRNGKey(0)).compile().cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        return float(ca.get('flops', 0.0)) if ca else None

    dots = step_flops('dots')
    nothing = step_flops('nothing')
    if not dots or not nothing:
        pytest.skip('cost_analysis unavailable')
    assert dots < nothing * 0.95, (dots, nothing)


# -- what a mirror stage keeps (``__mirror_stage__``, executor.py) ----------
# A tiny Nemotron-H (tests/test_nemotron_h.py's sizes, 'MEM*E': two
# ``SparseExperts`` layers, each in a stage of its own) with 8 of its 32
# experts held, so that the layer runs on the ladder of buffers as the
# benchmark's cells do.

def _tiny_nemotron(**changes):
    import test_nemotron_h as nh
    sizes = dict(nh.SIZES, experts_held=(0, 8), **changes)
    symbol = models.get_symbol('nemotron_h', seq_len=nh.T, **sizes)
    args, aux = nh.make_params(symbol, 0)
    return nh, symbol, args, aux


def _primitives(jaxpr, counts=None):
    """How often each primitive runs in ``jaxpr``, inside every sub-jaxpr
    at each place it is called (the printed form names a shared one once)."""
    import collections
    from jax.extend import core
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] += 1
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else [value]):
                if isinstance(sub, core.ClosedJaxpr):
                    _primitives(sub.jaxpr, counts)
                elif isinstance(sub, core.Jaxpr):
                    _primitives(sub, counts)
    return counts


def _step(symbol, args, aux, n, t):
    """The raw training step of ``symbol`` and its arguments."""
    import jax
    from mxnet_tpu.parallel.train_step import make_fit_step

    class GradsOut(object):
        def update(self, params, grads, state, lr_t):
            return params, grads

    step = make_fit_step(symbol, GradsOut(), data_names=('data',),
                         donate=False, _raw=True)
    batch = {'data': jnp.zeros((n, t)), 'softmax_label': jnp.zeros((n, t))}
    return step, (dict(args), {}, dict(aux), {}, batch, jnp.float32(0),
                  jax.random.PRNGKey(0))


def _step_jaxpr(symbol, args, aux, n, t):
    import jax
    step, given = _step(symbol, args, aux, n, t)
    return jax.make_jaxpr(step)(*given)


def test_a_stage_keeps_the_choice_and_the_order_of_sparse_experts(
        monkeypatch):
    """The backward pass runs no ``top_k``, ``sort`` or router product a
    second time: one of each a layer, where a stage that keeps nothing
    (a plain ``jax.checkpoint``, as before) runs two."""
    from mxnet_tpu import executor
    nh, symbol, args, aux = _tiny_nemotron()
    layers = nh.PATTERN.count('E')
    kept = _primitives(_step_jaxpr(symbol, args, aux, nh.N, nh.T).jaxpr)
    monkeypatch.setattr(executor, '_KEEP_POLICY', None)
    again = _primitives(_step_jaxpr(symbol, args, aux, nh.N, nh.T).jaxpr)
    # the marks are there either way, but only a stage that keeps them
    # leaves them out of its second pass
    assert (kept['name'], again['name']) == (4 * layers, 8 * layers)
    assert (kept['top_k'], kept['sort']) == (layers, layers)
    assert (again['top_k'], again['sort']) == (2 * layers, 2 * layers)
    assert kept['dot_general'] == again['dot_general'] - layers


def test_a_stage_that_marks_nothing_is_a_plain_checkpoint(monkeypatch):
    """An LFM2 with a convolution stage and a dense feed-forward stage
    lowers to the very program that ``jax.checkpoint(stage)`` without a
    policy gives; a stage with ``SparseExperts`` does not."""
    import jax
    import test_lfm2_moe as lfm2
    from mxnet_tpu import executor
    real = executor._KEEP_POLICY

    def lowered(symbol, args, aux, n, t, plain):
        monkeypatch.setattr(executor, '_KEEP_POLICY', None if plain else real)
        step, given = _step(symbol, args, aux, n, t)
        return jax.jit(step).lower(*given).as_text()

    symbol = models.get_symbol('lfm2_moe', seq_len=lfm2.T, **dict(
        lfm2.SIZES, layer_types=['conv'], num_dense_layers=1))
    args, aux = lfm2.make_params(symbol, 0)
    assert str(_step_jaxpr(symbol, args, aux, lfm2.N, lfm2.T)).count(
        'remat2') == 2
    assert lowered(symbol, args, aux, lfm2.N, lfm2.T, False) == \
        lowered(symbol, args, aux, lfm2.N, lfm2.T, True)
    nh, symbol, args, aux = _tiny_nemotron()
    assert lowered(symbol, args, aux, nh.N, nh.T, False) != \
        lowered(symbol, args, aux, nh.N, nh.T, True)


def test_three_steps_with_and_without_mirror_stages_agree():
    """What the stages keep changes no parameter: three SGD steps of the
    tiny model with and without ``__mirror_stage__``, within
    ``test_mirror_matches_unmirrored``'s tolerances."""
    import jax
    nh, symbol, args, aux = _tiny_nemotron()
    plain = mx.sym.load_json(symbol.tojson())
    for node in plain.topo_nodes():
        node._extra_attr.pop('__mirror_stage__', None)
    rng = np.random.default_rng(1)
    batch = {'data': jnp.asarray(rng.integers(0, nh.VOCAB, (nh.N, nh.T)),
                                 jnp.float32),
             'softmax_label': jnp.asarray(
                 rng.integers(0, nh.VOCAB, (nh.N, nh.T)), jnp.float32)}

    def three_steps(graph):
        step = make_train_step(graph, make_sgd_momentum(
            lr=0.01, momentum=0.9, wd=0.0, rescale_grad=1.0),
            ('data', 'softmax_label'), donate=False)
        params, state, moving = dict(args), sgd_momentum_init(args), \
            dict(aux)
        for _ in range(3):
            _, params, moving, state = step(params, moving, state, batch,
                                            jax.random.PRNGKey(0))
        return params

    staged, unstaged = three_steps(symbol), three_steps(plain)
    for name in unstaged:
        assert np.allclose(np.asarray(staged[name]),
                           np.asarray(unstaged[name]), rtol=1e-4,
                           atol=5e-5), name


def test_route_weights_are_the_chosen_scores_bit_for_bit():
    """``route``'s weights, the sigmoid of the chosen logits, and their
    gradient are ``take_along_axis(sigmoid(logits), chosen)``'s and its
    gradient's, bit for bit, where scores tie (four experts share their
    router rows and their bias).  Op by op: a compiled program may fuse
    the two forms apart by a rounding."""
    import jax
    from mxnet_tpu.ops import lm

    def scores_then_chosen(x, router, bias, k, normalise, scaling,
                           eps=1e-6):
        logits = jnp.dot(x.astype(jnp.float32), router.T,
                         precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(scores + bias, k)
        weights = jnp.take_along_axis(scores, chosen, axis=1)
        if normalise:
            weights = weights / (weights.sum(axis=-1, keepdims=True) + eps)
        return chosen, weights * scaling

    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    router = rng.standard_normal((32, 16)) / 4
    router[[3, 9, 17, 30]] = router[5]
    router = jnp.asarray(router, jnp.float32)
    bias = rng.standard_normal(32) * 0.1
    bias[[3, 9, 17, 30]] = bias[5]
    bias = jnp.asarray(bias, jnp.float32)
    cotangent = jnp.asarray(rng.standard_normal((64, 6)), jnp.float32)
    for normalise in (True, False):
        def loss(fn, x, router):
            chosen, weights = fn(x, router, bias, 6, normalise, 2.5)
            return (weights * cotangent).sum(), (chosen, weights)

        (_, (chosen, got)), grads = jax.value_and_grad(
            lambda *a: loss(lm.route, *a), argnums=(0, 1),
            has_aux=True)(x, router)
        (_, (want_chosen, want)), want_grads = jax.value_and_grad(
            lambda *a: loss(scores_then_chosen, *a), argnums=(0, 1),
            has_aux=True)(x, router)
        # rows that choose some of the five tied experts and not all: the
        # tie is broken there, and alike
        tied = np.isin(np.asarray(chosen), [3, 5, 9, 17, 30]).sum(axis=1)
        assert ((tied > 0) & (tied < 5)).sum() >= 4
        np.testing.assert_array_equal(np.asarray(chosen),
                                      np.asarray(want_chosen))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        for g, w in zip(grads, want_grads):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.fixture
def metrics_on():
    from mxnet_tpu import instrument
    before = instrument.metrics_enabled()
    instrument.reset_metrics()
    instrument.set_metrics(True)
    yield instrument
    instrument.set_metrics(before)
    instrument.reset_metrics()


def test_mirror_kept_counts_what_the_stages_mark_a_build(metrics_on):
    """4 a ``SparseExperts`` layer (the marks in the step's jaxpr), once a
    build however often it is traced; nothing for shape-only builds,
    evaluation programs and stages that mark nothing."""
    import jax
    import test_lfm2_moe as lfm2
    from mxnet_tpu.executor import _build_graph_fn
    nh, symbol, args, aux = _tiny_nemotron()
    layers = nh.PATTERN.count('E')

    def kept():
        return metrics_on.metrics_snapshot()['counters'].get(
            'executor.mirror_kept', 0)

    step, given = _step(symbol, args, aux, nh.N, nh.T)
    assert kept() == 0
    marks = _primitives(jax.make_jaxpr(step)(*given).jaxpr)['name']
    assert kept() == marks == 4 * layers
    jax.make_jaxpr(step)(*given)
    assert kept() == 4 * layers
    _step_jaxpr(symbol, args, aux, nh.N, nh.T)
    assert kept() == 8 * layers

    def trace(symbol, args, is_train, **kwargs):
        fn = _build_graph_fn(symbol, is_train, **kwargs)
        jax.eval_shape(fn, dict(args), dict(aux), jax.random.PRNGKey(0))

    inputs = dict(args, data=jnp.zeros((nh.N, nh.T)),
                  softmax_label=jnp.zeros((nh.N, nh.T)))
    trace(symbol, inputs, True, _count=False)
    trace(symbol, inputs, False)
    symbol = models.get_symbol('lfm2_moe', seq_len=lfm2.T, **dict(
        lfm2.SIZES, layer_types=['conv'], num_dense_layers=1))
    args, aux = lfm2.make_params(symbol, 0)
    trace(symbol, dict(args, data=jnp.zeros((lfm2.N, lfm2.T)),
                       softmax_label=jnp.zeros((lfm2.N, lfm2.T))), True)
    assert kept() == 8 * layers
