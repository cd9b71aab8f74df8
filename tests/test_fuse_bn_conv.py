"""BN->relu->1x1conv fusion pass (fuse.py): the rewritten graph must
match the unfused one bit-for-tolerance in forward, gradients and aux
updates, and the fused train step must track the unfused one."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import sym
from mxnet_tpu.fuse import fuse_bn_relu_conv1x1
from mxnet_tpu.executor import _build_graph_fn


def _net():
    data = sym.Variable('data')
    bn = sym.BatchNorm(data, fix_gamma=False, eps=1e-3, name='bn1')
    act = sym.Activation(bn, act_type='relu')
    conv = sym.Convolution(act, num_filter=8, kernel=(1, 1),
                           no_bias=True, name='conv1')
    # second, non-matching conv (3x3) stays unfused
    out = sym.Convolution(conv, num_filter=4, kernel=(3, 3), pad=(1, 1),
                          no_bias=True, name='conv2')
    return sym.SoftmaxOutput(sym.Flatten(
        sym.Pooling(out, global_pool=True, kernel=(2, 2),
                    pool_type='avg')), name='softmax')


def _values(seed=0):
    rng = np.random.RandomState(seed)
    return {
        'data': jnp.asarray(rng.randn(4, 6, 8, 8).astype(np.float32)),
        'bn1_gamma': jnp.asarray(rng.rand(6).astype(np.float32) + 0.5),
        'bn1_beta': jnp.asarray(rng.randn(6).astype(np.float32)),
        'conv1_weight': jnp.asarray(
            rng.randn(8, 6, 1, 1).astype(np.float32) * 0.3),
        'conv2_weight': jnp.asarray(
            rng.randn(4, 8, 3, 3).astype(np.float32) * 0.3),
        'softmax_label': jnp.asarray(
            rng.randint(0, 4, 4).astype(np.float32)),
    }


def _aux():
    return {'bn1_moving_mean': jnp.zeros(6),
            'bn1_moving_var': jnp.ones(6)}


def test_rewrite_structure():
    fused = fuse_bn_relu_conv1x1(_net())
    ops = [n.op for n in fused.topo_nodes() if not n.is_variable]
    assert '_bn_relu_conv' in ops
    assert 'BatchNorm' not in ops and 'Activation' not in ops
    assert ops.count('Convolution') == 1          # the 3x3 survives
    assert fused.list_arguments() == _net().list_arguments()
    assert fused.list_auxiliary_states() == \
        _net().list_auxiliary_states()


@pytest.mark.parametrize('is_train', [True, False])
def test_fused_matches_unfused(is_train):
    net = _net()
    fused = fuse_bn_relu_conv1x1(net)
    vals, aux = _values(), _aux()
    rng = jax.random.PRNGKey(0)
    f0 = _build_graph_fn(net, is_train)
    f1 = _build_graph_fn(fused, is_train)
    (o0, a0) = f0(vals, aux, rng)
    (o1, a1) = f1(vals, aux, rng)
    np.testing.assert_allclose(np.asarray(o0[0]), np.asarray(o1[0]),
                               rtol=1e-5, atol=1e-5)
    assert set(a0) == set(a1)
    for k in a0:
        np.testing.assert_allclose(np.asarray(a0[k]), np.asarray(a1[k]),
                                   rtol=1e-5, atol=1e-5)


def test_fused_gradients_match():
    net = _net()
    fused = fuse_bn_relu_conv1x1(net)
    vals, aux = _values(), _aux()
    rng = jax.random.PRNGKey(0)
    grad_keys = [k for k in vals if k not in ('data', 'softmax_label')]

    def make_loss(s):
        f = _build_graph_fn(s, True)

        def loss(p):
            merged = dict(vals)
            merged.update(p)
            outs, _ = f(merged, aux, rng)
            lab = jax.nn.one_hot(
                vals['softmax_label'].astype(jnp.int32), 4)
            return -jnp.mean(jnp.sum(
                lab * jnp.log(outs[0] + 1e-9), axis=1))
        return loss

    p = {k: vals[k] for k in grad_keys}
    g0 = jax.grad(make_loss(net))(p)
    g1 = jax.grad(make_loss(fused))(p)
    for k in grad_keys:
        np.testing.assert_allclose(np.asarray(g0[k]), np.asarray(g1[k]),
                                   rtol=1e-4, atol=1e-5, err_msg=k)


def test_fit_step_knob(monkeypatch):
    """MXTPU_FUSE=aggressive routes make_fit_step through the rewrite
    and parameters evolve identically to the unfused step."""
    from mxnet_tpu.parallel.train_step import (make_train_step,
                                               make_sgd_momentum,
                                               sgd_momentum_init)
    net = _net()
    vals, aux = _values(), _aux()
    params0 = {k: v for k, v in vals.items()
               if k not in ('data', 'softmax_label')}
    batch = {'data': vals['data'],
             'softmax_label': vals['softmax_label']}
    opt = make_sgd_momentum(lr=0.1, momentum=0.9, wd=0.0,
                            rescale_grad=0.25)
    key = jax.random.PRNGKey(0)
    results = {}
    for fuse_on in (False, True):
        if fuse_on:
            monkeypatch.setenv('MXTPU_FUSE', 'aggressive')
        else:
            monkeypatch.delenv('MXTPU_FUSE', raising=False)
        step = make_train_step(net, opt, ('data', 'softmax_label'),
                               donate=False)
        p, a, s = dict(params0), dict(aux), sgd_momentum_init(params0)
        for _ in range(3):
            _, p, a, s = step(p, a, s, batch, key)
        results[fuse_on] = {k: np.asarray(v) for k, v in p.items()}
    for k in results[False]:
        np.testing.assert_allclose(results[False][k], results[True][k],
                                   rtol=1e-4, atol=1e-5, err_msg=k)


def test_resnet50_fusion_coverage():
    """The pass must catch every bottleneck conv in ResNet-50 —
    1x1 s1/s2 and 3x3 s1/s2, shared-relu projections included —
    52 of 53 convs (only the stem survives) and preserve the
    forward."""
    from mxnet_tpu import models
    s = models.get_symbol('resnet-50', num_classes=10,
                          image_shape=(3, 64, 64))
    fused = fuse_bn_relu_conv1x1(s)
    ops = [n.op for n in fused.topo_nodes() if not n.is_variable]
    assert ops.count('_bn_relu_conv') == 52
    assert ops.count('Convolution') == 1   # only the stem survives

    dshape = (2, 3, 64, 64)
    arg_shapes, _, aux_shapes = s.infer_shape(data=dshape)
    rng = np.random.RandomState(0)
    vals = {n: jnp.asarray(rng.normal(0, 0.05, sh).astype(np.float32))
            for n, sh in zip(s.list_arguments(), arg_shapes)}
    vals['data'] = jnp.asarray(rng.rand(*dshape).astype(np.float32))
    vals['softmax_label'] = jnp.asarray(
        rng.randint(0, 10, 2).astype(np.float32))
    aux = {n: (jnp.ones(sh) if 'var' in n else jnp.zeros(sh))
           for n, sh in zip(s.list_auxiliary_states(), aux_shapes)}
    key = jax.random.PRNGKey(0)
    o0, _ = _build_graph_fn(s, True)(vals, aux, key)
    o1, _ = _build_graph_fn(fused, True)(vals, aux, key)
    np.testing.assert_allclose(np.asarray(o0[0]), np.asarray(o1[0]),
                               rtol=1e-5, atol=1e-6)


def _shape_class_net(kernel, stride, shortcut=False):
    """BN->relu->conv chain for one conv shape class; with
    ``shortcut`` the relu feeds TWO fusable convs (ResNet's shared
    unit-entry pattern) whose sum is the head."""
    data = sym.Variable('data')
    bn = sym.BatchNorm(data, fix_gamma=False, eps=1e-3, name='bn1')
    act = sym.Activation(bn, act_type='relu')
    pad = (1, 1) if kernel == (3, 3) else (0, 0)
    conv = sym.Convolution(act, num_filter=8, kernel=kernel,
                           stride=stride, pad=pad, no_bias=True,
                           name='conv1')
    if shortcut:
        sc = sym.Convolution(act, num_filter=8, kernel=(1, 1),
                             stride=stride, no_bias=True, name='sc')
        conv = conv + sc
    return sym.SoftmaxOutput(sym.Flatten(
        sym.Pooling(conv, global_pool=True, kernel=(2, 2),
                    pool_type='avg')), name='softmax')


@pytest.mark.parametrize('kernel,stride,shortcut', [
    ((3, 3), (1, 1), False),
    ((3, 3), (2, 2), False),
    ((1, 1), (2, 2), False),
    ((3, 3), (2, 2), True),      # shared relu: conv + projection
])
def test_shape_classes_match(kernel, stride, shortcut):
    """Every fusable conv shape class: fwd, aux updates and gradients
    must match the unfused graph."""
    from mxnet_tpu.fuse import fuse_bn_relu_conv
    net = _shape_class_net(kernel, stride, shortcut)
    fused = fuse_bn_relu_conv(net)
    fused_ops = [n.op for n in fused.topo_nodes() if not n.is_variable]
    assert fused_ops.count('_bn_relu_conv') == (2 if shortcut else 1)
    assert 'BatchNorm' not in fused_ops

    vals, aux = _values(), _aux()
    if shortcut:
        rng0 = np.random.RandomState(3)
        vals['sc_weight'] = jnp.asarray(
            rng0.randn(8, 6, 1, 1).astype(np.float32) * 0.3)
    vals['conv1_weight'] = jnp.asarray(
        np.random.RandomState(2).randn(8, 6, *kernel).astype(
            np.float32) * 0.3)
    rng = jax.random.PRNGKey(0)
    for is_train in (True, False):
        o0, a0 = _build_graph_fn(net, is_train)(vals, aux, rng)
        o1, a1 = _build_graph_fn(fused, is_train)(vals, aux, rng)
        np.testing.assert_allclose(np.asarray(o0[0]), np.asarray(o1[0]),
                                   rtol=1e-5, atol=1e-5)
        assert set(a0) == set(a1)
        for k in a0:
            np.testing.assert_allclose(np.asarray(a0[k]),
                                       np.asarray(a1[k]),
                                       rtol=1e-5, atol=1e-5)

    grad_keys = [k for k in vals if k not in ('data', 'softmax_label')]

    def make_loss(s):
        f = _build_graph_fn(s, True)

        def loss(p):
            merged = dict(vals)
            merged.update(p)
            outs, _ = f(merged, aux, rng)
            lab = jax.nn.one_hot(
                vals['softmax_label'].astype(jnp.int32),
                outs[0].shape[1])
            return -jnp.mean(jnp.sum(
                lab * jnp.log(outs[0] + 1e-9), axis=1))
        return loss

    p = {k: vals[k] for k in grad_keys}
    g0 = jax.grad(make_loss(net))(p)
    g1 = jax.grad(make_loss(fused))(p)
    for k in grad_keys:
        np.testing.assert_allclose(np.asarray(g0[k]), np.asarray(g1[k]),
                                   rtol=1e-4, atol=1e-5, err_msg=k)


def test_unfusable_consumer_blocks_chain():
    """If the shared relu also feeds a NON-conv consumer the chain must
    stay unfused (fusing would be traffic-neutral)."""
    from mxnet_tpu.fuse import fuse_bn_relu_conv
    data = sym.Variable('data')
    bn = sym.BatchNorm(data, fix_gamma=False, name='bn1')
    act = sym.Activation(bn, act_type='relu')
    conv = sym.Convolution(act, num_filter=8, kernel=(1, 1),
                           no_bias=True, name='conv1')
    # biased conv is not fusable -> the shared relu must materialize
    conv2 = sym.Convolution(act, num_filter=8, kernel=(1, 1),
                            no_bias=False, name='conv2')
    net = sym.SoftmaxOutput(sym.Flatten(
        sym.Pooling(conv + conv2, global_pool=True, kernel=(2, 2),
                    pool_type='avg')), name='softmax')
    fused = fuse_bn_relu_conv(net)
    ops = [n.op for n in fused.topo_nodes() if not n.is_variable]
    assert '_bn_relu_conv' not in ops
    assert 'BatchNorm' in ops


def test_eval_step_knob(monkeypatch):
    """Inference under the knob (moving-stats path) matches unfused."""
    from mxnet_tpu.parallel.train_step import make_eval_step
    net = _net()
    vals, aux = _values(), _aux()
    params = {k: v for k, v in vals.items()
              if k not in ('data', 'softmax_label')}
    batch = {'data': vals['data'],
             'softmax_label': vals['softmax_label']}
    key = jax.random.PRNGKey(0)
    outs = {}
    for on in (False, True):
        if on:
            monkeypatch.setenv('MXTPU_FUSE', 'aggressive')
        else:
            monkeypatch.delenv('MXTPU_FUSE', raising=False)
        outs[on] = np.asarray(
            make_eval_step(net)(params, aux, batch, key)[0])
    np.testing.assert_allclose(outs[False], outs[True],
                               rtol=1e-5, atol=1e-6)


def test_fold_conv_bn_inference_matches():
    """Post-norm conv->bn(->relu) folds into the conv at eval: exact
    numerics vs the unfused graph, on the inception/classic-stem
    pattern the pre-act pass cannot touch."""
    from mxnet_tpu.fuse import fold_conv_bn_inference
    rng0 = np.random.RandomState(7)
    data = sym.Variable('data')
    conv = sym.Convolution(data, num_filter=8, kernel=(3, 3),
                           pad=(1, 1), no_bias=True, name='conv1')
    bn = sym.BatchNorm(conv, fix_gamma=False, eps=1e-3, name='bn1')
    act = sym.Activation(bn, act_type='relu')
    net = sym.SoftmaxOutput(sym.Flatten(
        sym.Pooling(act, global_pool=True, kernel=(2, 2),
                    pool_type='avg')), name='softmax')
    folded = fold_conv_bn_inference(net)
    ops = [n.op for n in folded.topo_nodes() if not n.is_variable]
    assert '_conv_bn_folded' in ops
    assert 'Convolution' not in ops and 'BatchNorm' not in ops
    assert folded.list_arguments() == net.list_arguments()

    vals = {
        'data': jnp.asarray(rng0.randn(2, 6, 8, 8).astype(np.float32)),
        'conv1_weight': jnp.asarray(
            rng0.randn(8, 6, 3, 3).astype(np.float32) * 0.3),
        'bn1_gamma': jnp.asarray(rng0.rand(8).astype(np.float32) + 0.5),
        'bn1_beta': jnp.asarray(rng0.randn(8).astype(np.float32)),
        'softmax_label': jnp.asarray(
            rng0.randint(0, 8, 2).astype(np.float32)),
    }
    aux = {'bn1_moving_mean': jnp.asarray(
               rng0.randn(8).astype(np.float32) * 0.1),
           'bn1_moving_var': jnp.asarray(
               rng0.rand(8).astype(np.float32) + 0.5)}
    rng = jax.random.PRNGKey(0)
    o0, _ = _build_graph_fn(net, False)(vals, aux, rng)
    o1, _ = _build_graph_fn(folded, False)(vals, aux, rng)
    np.testing.assert_allclose(np.asarray(o0[0]), np.asarray(o1[0]),
                               rtol=1e-5, atol=1e-5)


def test_eval_knob_applies_both_passes(monkeypatch):
    """make_eval_step under the knob runs BOTH rewrites and matches
    unfused on a net with pre-act AND post-norm chains."""
    from mxnet_tpu.parallel.train_step import make_eval_step
    rng0 = np.random.RandomState(9)
    data = sym.Variable('data')
    # post-norm stem: conv -> bn -> relu
    c0 = sym.Convolution(data, num_filter=6, kernel=(3, 3), pad=(1, 1),
                         no_bias=True, name='c0')
    b0 = sym.BatchNorm(c0, fix_gamma=False, name='b0')
    a0 = sym.Activation(b0, act_type='relu')
    # pre-act chain: bn -> relu -> conv
    b1 = sym.BatchNorm(a0, fix_gamma=False, name='b1')
    a1 = sym.Activation(b1, act_type='relu')
    c1 = sym.Convolution(a1, num_filter=8, kernel=(1, 1), no_bias=True,
                         name='c1')
    net = sym.SoftmaxOutput(sym.Flatten(
        sym.Pooling(c1, global_pool=True, kernel=(2, 2),
                    pool_type='avg')), name='softmax')
    shapes = dict(zip(net.list_arguments(),
                      net.infer_shape(data=(2, 3, 8, 8))[0]))
    params = {n: jnp.asarray(rng0.randn(*s).astype(np.float32) * 0.3)
              for n, s in shapes.items()
              if n not in ('data', 'softmax_label')}
    aux = {n: (jnp.ones(s) if 'var' in n else
               jnp.asarray(rng0.randn(*s).astype(np.float32) * 0.1))
           for n, s in zip(net.list_auxiliary_states(),
                           net.infer_shape(data=(2, 3, 8, 8))[2])}
    batch = {'data': jnp.asarray(
                 rng0.rand(2, 3, 8, 8).astype(np.float32)),
             'softmax_label': jnp.zeros(2, jnp.float32)}
    key = jax.random.PRNGKey(0)
    outs = {}
    for on in (False, True):
        if on:
            monkeypatch.setenv('MXTPU_FUSE', 'aggressive')
        else:
            monkeypatch.delenv('MXTPU_FUSE', raising=False)
        outs[on] = np.asarray(
            make_eval_step(net)(params, aux, batch, key)[0])
    np.testing.assert_allclose(outs[False], outs[True], rtol=1e-5,
                               atol=1e-5)


def test_fold_biased_conv_bn():
    """Biased conv -> bn folds too (inception-bn / inception-resnet-v2
    family): bn(conv+c) = conv(x, w*s) + (beta + (c - mean)*s)."""
    from mxnet_tpu.fuse import fold_conv_bn_inference
    rng0 = np.random.RandomState(11)
    data = sym.Variable('data')
    conv = sym.Convolution(data, num_filter=5, kernel=(1, 1),
                           name='cv')          # no_bias=False default
    bn = sym.BatchNorm(conv, fix_gamma=True, name='bnv')
    net = sym.SoftmaxOutput(sym.Flatten(
        sym.Pooling(bn, global_pool=True, kernel=(2, 2),
                    pool_type='avg')), name='softmax')
    folded = fold_conv_bn_inference(net)
    ops = [n.op for n in folded.topo_nodes() if not n.is_variable]
    assert '_conv_bn_folded' in ops and 'BatchNorm' not in ops
    assert folded.list_arguments() == net.list_arguments()
    vals = {
        'data': jnp.asarray(rng0.randn(3, 4, 6, 6).astype(np.float32)),
        'cv_weight': jnp.asarray(
            rng0.randn(5, 4, 1, 1).astype(np.float32) * 0.4),
        'cv_bias': jnp.asarray(rng0.randn(5).astype(np.float32)),
        'bnv_gamma': jnp.asarray(rng0.rand(5).astype(np.float32) + 0.5),
        'bnv_beta': jnp.asarray(rng0.randn(5).astype(np.float32)),
        'softmax_label': jnp.zeros(3, jnp.float32),
    }
    aux = {'bnv_moving_mean': jnp.asarray(
               rng0.randn(5).astype(np.float32) * 0.2),
           'bnv_moving_var': jnp.asarray(
               rng0.rand(5).astype(np.float32) + 0.5)}
    rng = jax.random.PRNGKey(0)
    o0, _ = _build_graph_fn(net, False)(vals, aux, rng)
    o1, _ = _build_graph_fn(folded, False)(vals, aux, rng)
    np.testing.assert_allclose(np.asarray(o0[0]), np.asarray(o1[0]),
                               rtol=1e-5, atol=1e-5)


def test_folded_graph_infers_from_data_alone():
    """simple_bind-style inference on a folded graph: weight from
    num_filter/kernel, gamma/beta/aux from num_filter (the aux_shape
    hook — the generic heuristic would wrongly use data channels)."""
    from mxnet_tpu.fuse import fold_conv_bn_inference
    d = sym.Variable('data')
    c = sym.Convolution(d, num_filter=5, kernel=(3, 3), pad=(1, 1),
                        name='cv')
    b = sym.BatchNorm(c, name='bn')
    net = sym.SoftmaxOutput(sym.Flatten(
        sym.Pooling(b, global_pool=True, kernel=(2, 2),
                    pool_type='avg')), name='softmax')
    folded = fold_conv_bn_inference(net)
    args, outs, aux = folded.infer_shape(data=(2, 4, 8, 8))
    shapes = dict(zip(folded.list_arguments(), args))
    assert shapes['cv_weight'] == (5, 4, 3, 3)
    assert shapes['bn_gamma'] == (5,)
    assert dict(zip(folded.list_auxiliary_states(), aux)) == {
        'bn_moving_mean': (5,), 'bn_moving_var': (5,)}


@pytest.mark.parametrize('name,image', [
    ('resnet-18', 64), ('resnext', 64), ('inception-bn', 64),
    ('inception-v3', 80), ('inception-resnet-v2', 80),
    ('googlenet', 64),
])
def test_zoo_models_fuse_forward_parity(name, image):
    """The fuse + NHWC-region passes must be safe on every zoo family
    (grouped convs, concat trees, post-norm stems): building the fused
    graph and running a tiny forward must match the unfused graph.

    Runs in EVAL mode: train-mode comparison is doubly unsound here —
    the fuse pass shifts node indices so stochastic ops (inception-
    resnet-v2's Dropout) draw different masks, and with batch
    statistics these deep graphs chaotically amplify float32
    reassociation noise (the unfused inception-v3 maps 1e-7 input
    noise to ~2e-2 output delta, measured).  Eval is deterministic:
    dropout is identity, BN uses moving stats.  Per-shape-class
    train-mode exactness is pinned by the dedicated tests above; this
    test guards against STRUCTURAL breakage across model families."""
    from mxnet_tpu import models
    s = models.get_symbol(name, num_classes=10,
                          image_shape=(3, image, image))
    fused = fuse_bn_relu_conv1x1(s)
    dshape = (2, 3, image, image)
    arg_shapes, _, aux_shapes = s.infer_shape(data=dshape)
    rng = np.random.RandomState(0)

    def init(name_, sh):
        if name_.endswith('_gamma'):
            return jnp.ones(sh, jnp.float32)
        if name_.endswith(('_beta', '_bias')):
            return jnp.zeros(sh, jnp.float32)
        fan_in = int(np.prod(sh[1:])) if len(sh) > 1 else sh[0]
        std = np.sqrt(2.0 / max(fan_in, 1))
        return jnp.asarray(
            rng.normal(0, std, sh).astype(np.float32))

    vals = {n: init(n, sh)
            for n, sh in zip(s.list_arguments(), arg_shapes)}
    vals['data'] = jnp.asarray(
        rng.rand(*dshape).astype(np.float32))
    vals['softmax_label'] = jnp.asarray(
        rng.randint(0, 10, 2).astype(np.float32))
    aux = {n: (jnp.ones(sh) if 'var' in n else jnp.zeros(sh))
           for n, sh in zip(s.list_auxiliary_states(), aux_shapes)}
    key = jax.random.PRNGKey(0)
    o0, _ = _build_graph_fn(s, False)(vals, aux, key)
    o1, _ = _build_graph_fn(fused, False)(vals, aux, key)
    a, b = np.asarray(o0[0]), np.asarray(o1[0])
    np.testing.assert_allclose(a, b, atol=1e-3)
