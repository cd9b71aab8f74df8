"""Operator forward/backward checks
(reference tests/python/unittest/test_operator.py — numeric-gradient and
forward checks per op via test_utils)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import sym, nd
from mxnet_tpu.test_utils import (check_numeric_gradient,
                                  check_symbolic_forward,
                                  check_symbolic_backward, reldiff,
                                  assert_almost_equal)

RNG = np.random.RandomState(7)


def test_elemwise_forward():
    shape = (3, 4)
    x = RNG.rand(*shape).astype(np.float32) + 0.5
    for name, ref in [('exp', np.exp), ('log', np.log), ('sqrt', np.sqrt),
                      ('square', np.square), ('tanh', np.tanh),
                      ('sigmoid', lambda v: 1 / (1 + np.exp(-v)))]:
        data = sym.Variable('data')
        out = getattr(sym, name)(data)
        check_symbolic_forward(out, {'data': x}, [ref(x)], check_eps=1e-5)


def test_elemwise_grad():
    x = RNG.rand(3, 4).astype(np.float32) + 0.5
    for name in ['exp', 'log', 'sqrt', 'square', 'tanh', 'sigmoid',
                 'sin', 'cos']:
        data = sym.Variable('data')
        out = getattr(sym, name)(data)
        check_numeric_gradient(out, {'data': x}, numeric_eps=1e-3,
                               check_eps=0.02)


def test_binary_ops():
    a = RNG.rand(3, 4).astype(np.float32) + 0.5
    b = RNG.rand(3, 4).astype(np.float32) + 0.5
    lhs, rhs = sym.Variable('lhs'), sym.Variable('rhs')
    for op, ref in [(sym.elemwise_add, a + b), (sym.elemwise_sub, a - b),
                    (sym.elemwise_mul, a * b), (sym.elemwise_div, a / b)]:
        out = op(lhs, rhs)
        check_symbolic_forward(out, {'lhs': a, 'rhs': b}, [ref],
                               check_eps=1e-5)
        check_numeric_gradient(out, {'lhs': a, 'rhs': b}, check_eps=0.02)


def test_dot_grad():
    a = RNG.rand(4, 5).astype(np.float32)
    b = RNG.rand(5, 3).astype(np.float32)
    out = sym.dot(sym.Variable('lhs'), sym.Variable('rhs'))
    check_symbolic_forward(out, {'lhs': a, 'rhs': b}, [a @ b], 1e-4)
    check_numeric_gradient(out, {'lhs': a, 'rhs': b}, check_eps=0.05)


def test_fully_connected():
    x = RNG.rand(5, 10).astype(np.float32)
    w = RNG.rand(4, 10).astype(np.float32)
    b = RNG.rand(4).astype(np.float32)
    fc = sym.FullyConnected(sym.Variable('data'), num_hidden=4, name='fc')
    check_symbolic_forward(fc, {'data': x, 'fc_weight': w, 'fc_bias': b},
                           [x @ w.T + b], 1e-4)
    check_numeric_gradient(fc, {'data': x, 'fc_weight': w, 'fc_bias': b},
                           check_eps=0.05)


def test_activation_relu_grad():
    x = RNG.randn(4, 6).astype(np.float32)
    out = sym.Activation(sym.Variable('data'), act_type='relu')
    # known closed-form backward
    y = np.maximum(x, 0)
    check_symbolic_forward(out, {'data': x}, [y], 1e-5)
    og = RNG.rand(4, 6).astype(np.float32)
    check_symbolic_backward(out, {'data': x}, [og], [og * (x > 0)], 1e-4)


def test_convolution_forward():
    # compare against explicit correlation
    x = RNG.rand(1, 1, 5, 5).astype(np.float32)
    w = RNG.rand(1, 1, 3, 3).astype(np.float32)
    conv = sym.Convolution(sym.Variable('data'), num_filter=1,
                           kernel=(3, 3), no_bias=True, name='c')
    expected = np.zeros((1, 1, 3, 3), np.float32)
    for i in range(3):
        for j in range(3):
            expected[0, 0, i, j] = np.sum(x[0, 0, i:i + 3, j:j + 3] *
                                          w[0, 0])
    check_symbolic_forward(conv, {'data': x, 'c_weight': w}, [expected],
                           1e-4)


def test_convolution_grad():
    x = RNG.rand(2, 3, 7, 7).astype(np.float32)
    conv = sym.Convolution(sym.Variable('data'), num_filter=4,
                           kernel=(3, 3), pad=(1, 1), name='c')
    w = RNG.rand(4, 3, 3, 3).astype(np.float32) * 0.1
    b = RNG.rand(4).astype(np.float32) * 0.1
    check_numeric_gradient(conv, {'data': x, 'c_weight': w, 'c_bias': b},
                           numeric_eps=1e-2, check_eps=0.05)


def test_pooling():
    x = np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4)
    pool = sym.Pooling(sym.Variable('data'), kernel=(2, 2), stride=(2, 2),
                       pool_type='max')
    expected = np.array([[[[5, 7], [13, 15]]]], np.float32)
    check_symbolic_forward(pool, {'data': x}, [expected], 1e-5)
    avg = sym.Pooling(sym.Variable('data'), kernel=(2, 2), stride=(2, 2),
                      pool_type='avg')
    expected_avg = np.array([[[[2.5, 4.5], [10.5, 12.5]]]], np.float32)
    check_symbolic_forward(avg, {'data': x}, [expected_avg], 1e-5)
    gpool = sym.Pooling(sym.Variable('data'), kernel=(1, 1),
                        global_pool=True, pool_type='max')
    check_symbolic_forward(gpool, {'data': x},
                           [np.array([[[[15.0]]]], np.float32)], 1e-5)


def test_softmax_output_grad():
    # SoftmaxOutput backward = (softmax - onehot) / ignores out_grad
    x = RNG.rand(4, 3).astype(np.float32)
    label = np.array([0, 1, 2, 1], np.float32)
    s = sym.SoftmaxOutput(sym.Variable('data'), sym.Variable('label'),
                          name='sm')
    ex = s.bind(mx.cpu(), {'data': nd.array(x), 'label': nd.array(label)},
                args_grad={'data': nd.zeros((4, 3))},
                grad_req={'data': 'write', 'label': 'null'})
    out = ex.forward(is_train=True)[0].asnumpy()
    expected_out = np.exp(x) / np.exp(x).sum(1, keepdims=True)
    assert reldiff(out, expected_out) < 1e-5
    ex.backward()
    onehot = np.eye(3, dtype=np.float32)[label.astype(int)]
    assert reldiff(ex.grad_dict['data'].asnumpy(),
                   expected_out - onehot) < 1e-5


def test_regression_grad():
    x = RNG.rand(4, 3).astype(np.float32)
    y = RNG.rand(4, 3).astype(np.float32)
    lin = sym.LinearRegressionOutput(sym.Variable('data'),
                                     sym.Variable('label'), name='lr')
    ex = lin.bind(mx.cpu(), {'data': nd.array(x), 'label': nd.array(y)},
                  args_grad={'data': nd.zeros((4, 3))},
                  grad_req={'data': 'write', 'label': 'null'})
    out = ex.forward(is_train=True)[0].asnumpy()
    assert np.allclose(out, x)
    ex.backward()
    assert reldiff(ex.grad_dict['data'].asnumpy(), (x - y) / 3.0) < 1e-5


def test_batchnorm_train_stats():
    x = RNG.rand(8, 3, 4, 4).astype(np.float32) * 5
    bn = sym.BatchNorm(sym.Variable('data'), name='bn', momentum=0.5,
                       fix_gamma=False)
    ex = bn.simple_bind(mx.cpu(), data=x.shape)
    ex.arg_dict['data'][:] = x
    ex.arg_dict['bn_gamma'][:] = 1.0
    ex.aux_dict['bn_moving_var'][:] = 1.0
    out = ex.forward(is_train=True)[0].asnumpy()
    # normalized output has ~0 mean / ~1 var per channel
    assert np.abs(out.mean(axis=(0, 2, 3))).max() < 1e-4
    assert np.abs(out.var(axis=(0, 2, 3)) - 1).max() < 1e-2
    # moving stats updated toward batch stats
    mm = ex.aux_dict['bn_moving_mean'].asnumpy()
    batch_mean = x.mean(axis=(0, 2, 3))
    assert reldiff(mm, 0.5 * batch_mean) < 1e-4


def test_batchnorm_grad():
    x = RNG.rand(4, 2, 3, 3).astype(np.float32)
    bn = sym.BatchNorm(sym.Variable('data'), name='bn', fix_gamma=False)
    gamma = np.ones(2, np.float32)
    beta = np.zeros(2, np.float32)
    check_numeric_gradient(
        bn, {'data': x, 'bn_gamma': gamma, 'bn_beta': beta},
        aux_states={'bn_moving_mean': np.zeros(2, np.float32),
                    'bn_moving_var': np.ones(2, np.float32)},
        numeric_eps=1e-2, check_eps=0.05)


def test_dropout():
    x = np.ones((100, 100), np.float32)
    drop = sym.Dropout(sym.Variable('data'), p=0.5)
    ex = drop.bind(mx.cpu(), {'data': nd.array(x)})
    out_eval = ex.forward(is_train=False)[0].asnumpy()
    assert np.allclose(out_eval, x)
    out_train = ex.forward(is_train=True)[0].asnumpy()
    frac_zero = (out_train == 0).mean()
    assert 0.4 < frac_zero < 0.6
    # scaled: surviving entries are 1/keep
    assert np.allclose(out_train[out_train != 0], 2.0)


def test_concat_slice_channel():
    a = RNG.rand(2, 3).astype(np.float32)
    b = RNG.rand(2, 3).astype(np.float32)
    cat = sym.Concat(sym.Variable('a'), sym.Variable('b'), dim=1)
    check_symbolic_forward(cat, {'a': a, 'b': b},
                           [np.concatenate([a, b], axis=1)], 1e-6)
    check_numeric_gradient(cat, {'a': a, 'b': b}, check_eps=0.02)
    x = RNG.rand(2, 6).astype(np.float32)
    sp = sym.SliceChannel(sym.Variable('data'), num_outputs=3, axis=1)
    ex = sp.bind(mx.cpu(), {'data': nd.array(x)})
    outs = ex.forward()
    assert len(outs) == 3
    assert np.allclose(outs[1].asnumpy(), x[:, 2:4])


def test_embedding():
    idx = np.array([0, 2, 1], np.float32)
    w = RNG.rand(3, 4).astype(np.float32)
    emb = sym.Embedding(sym.Variable('data'), input_dim=3, output_dim=4,
                        name='emb')
    check_symbolic_forward(emb, {'data': idx, 'emb_weight': w},
                           [w[idx.astype(int)]], 1e-6)


def test_transpose_swapaxis():
    x = RNG.rand(2, 3, 4).astype(np.float32)
    t = sym.transpose(sym.Variable('data'), axes=(2, 0, 1))
    check_symbolic_forward(t, {'data': x}, [x.transpose(2, 0, 1)], 1e-6)
    s = sym.SwapAxis(sym.Variable('data'), dim1=0, dim2=2)
    check_symbolic_forward(s, {'data': x}, [x.swapaxes(0, 2)], 1e-6)


def test_reduce_ops():
    x = RNG.rand(2, 3, 4).astype(np.float32)
    for name, ref in [('sum', np.sum), ('max', np.max), ('min', np.min),
                      ('mean', np.mean), ('prod', np.prod)]:
        out = getattr(sym, name)(sym.Variable('data'), axis=1)
        check_symbolic_forward(out, {'data': x}, [ref(x, axis=1)], 1e-4)
        out_keep = getattr(sym, name)(sym.Variable('data'), axis=(0, 2),
                                      keepdims=True)
        check_symbolic_forward(out_keep, {'data': x},
                               [ref(x, axis=(0, 2), keepdims=True)], 1e-4)


def test_sum_grad():
    x = RNG.rand(3, 4).astype(np.float32)
    out = sym.sum(sym.Variable('data'), axis=1)
    check_numeric_gradient(out, {'data': x}, check_eps=0.02)


def test_broadcast_grad():
    a = RNG.rand(2, 1).astype(np.float32)
    b = RNG.rand(2, 3).astype(np.float32)
    out = sym.broadcast_mul(sym.Variable('lhs'), sym.Variable('rhs'))
    check_symbolic_forward(out, {'lhs': a, 'rhs': b}, [a * b], 1e-5)
    check_numeric_gradient(out, {'lhs': a, 'rhs': b}, check_eps=0.03)


def test_leaky_relu():
    x = RNG.randn(4, 5).astype(np.float32)
    leaky = sym.LeakyReLU(sym.Variable('data'), act_type='leaky', slope=0.1)
    check_symbolic_forward(leaky, {'data': x},
                           [np.where(x > 0, x, 0.1 * x)], 1e-5)
    elu = sym.LeakyReLU(sym.Variable('data'), act_type='elu', slope=0.3)
    check_symbolic_forward(elu, {'data': x},
                           [np.where(x > 0, x, 0.3 * (np.exp(x) - 1))],
                           1e-5)


def test_upsampling():
    x = np.arange(4, dtype=np.float32).reshape(1, 1, 2, 2)
    up = sym.UpSampling(sym.Variable('data'), scale=2,
                        sample_type='nearest')
    expected = x.repeat(2, axis=2).repeat(2, axis=3)
    check_symbolic_forward(up, {'data': x}, [expected], 1e-6)


def test_block_grad():
    x = RNG.rand(3, 3).astype(np.float32)
    v = sym.Variable('data')
    blocked = sym.BlockGrad(v) * 2.0 + v
    ex = blocked.bind(mx.cpu(), {'data': nd.array(x)},
                      args_grad={'data': nd.zeros((3, 3))})
    ex.forward(is_train=True)
    ex.backward(nd.ones((3, 3)))
    # gradient flows only through the un-blocked path
    assert np.allclose(ex.grad_dict['data'].asnumpy(), 1.0)


def test_where():
    cond = np.array([[1, 0], [0, 1]], np.float32)
    a = np.full((2, 2), 5.0, np.float32)
    b = np.full((2, 2), -5.0, np.float32)
    out = sym.where(sym.Variable('condition'), sym.Variable('x'),
                    sym.Variable('y'))
    check_symbolic_forward(out, {'condition': cond, 'x': a, 'y': b},
                           [np.where(cond > 0, a, b)], 1e-6)


def test_grad_req_add():
    x = RNG.rand(3, 3).astype(np.float32)
    out = sym.square(sym.Variable('data'))
    init_grad = RNG.rand(3, 3).astype(np.float32)
    g = nd.array(init_grad.copy())
    ex = out.bind(mx.cpu(), {'data': nd.array(x)}, args_grad={'data': g},
                  grad_req='add')
    ex.forward(is_train=True)
    ex.backward(nd.ones((3, 3)))
    assert reldiff(g.asnumpy(), init_grad + 2 * x) < 1e-5


def test_sequence_ops():
    x = RNG.rand(4, 3, 2).astype(np.float32)   # (T, N, C)
    lengths = np.array([2, 4, 1], np.float32)
    last = sym.SequenceLast(sym.Variable('data'),
                            sym.Variable('sequence_length'),
                            use_sequence_length=True)
    expected = np.stack([x[1, 0], x[3, 1], x[0, 2]])
    check_symbolic_forward(last, {'data': x, 'sequence_length': lengths},
                           [expected], 1e-6)
    mask = sym.SequenceMask(sym.Variable('data'),
                            sym.Variable('sequence_length'),
                            use_sequence_length=True, value=-1.0)
    em = x.copy()
    em[2:, 0] = -1.0
    em[1:, 2] = -1.0
    check_symbolic_forward(mask, {'data': x, 'sequence_length': lengths},
                           [em], 1e-6)


def test_lrn():
    x = RNG.rand(2, 8, 4, 4).astype(np.float32)
    lrn = sym.LRN(sym.Variable('data'), nsize=5)
    ex = lrn.bind(mx.cpu(), {'data': nd.array(x)})
    out = ex.forward()[0].asnumpy()
    assert out.shape == x.shape
    assert (np.abs(out) <= np.abs(x) + 1e-5).all()


def test_l2_normalization():
    x = RNG.rand(3, 4).astype(np.float32)
    l2 = sym.L2Normalization(sym.Variable('data'), mode='instance')
    out_ref = x / np.sqrt((x ** 2).sum(axis=1, keepdims=True) + 1e-10)
    check_symbolic_forward(l2, {'data': x}, [out_ref], 1e-5)


def test_pick_and_element_0index():
    x = RNG.rand(4, 5).astype(np.float32)
    idx = np.array([0, 2, 4, 1], dtype=np.float32)
    expected = x[np.arange(4), idx.astype(int)]
    pick = sym.pick(sym.Variable('data'), sym.Variable('index'))
    check_symbolic_forward(pick, {'data': x, 'index': idx}, [expected], 1e-6)
    choose = sym.choose_element_0index(sym.Variable('lhs'), sym.Variable('rhs'))
    check_symbolic_forward(choose, {'lhs': x, 'rhs': idx}, [expected], 1e-6)
    vals = np.full(4, 7.0, dtype=np.float32)
    filled = nd.fill_element_0index(nd.array(x), nd.array(vals),
                                    nd.array(idx)).asnumpy()
    ef = x.copy()
    ef[np.arange(4), idx.astype(int)] = 7.0
    assert np.allclose(filled, ef)


def test_stack_diag_misc_unary():
    x = RNG.rand(3, 4).astype(np.float32)
    out = nd.stack(nd.array(x), nd.array(x), num_args=2, axis=1).asnumpy()
    assert out.shape == (3, 2, 4)
    assert np.allclose(out[:, 0], x)
    assert np.allclose(nd.diag(nd.array(x)).asnumpy(), np.diag(x))
    assert np.allclose(nd.reciprocal(nd.array(x + 1)).asnumpy(),
                       1.0 / (x + 1), atol=1e-6)
    assert np.allclose(nd.trunc(nd.array(x * 4 - 2)).asnumpy(),
                       np.trunc(x * 4 - 2))


def test_slice_assign_ops():
    """_slice_assign/_crop_assign_scalar (matrix_op.cc:222,247)."""
    x = RNG.rand(3, 4).astype(np.float32)
    v = np.full((2, 2), 9.0, np.float32)
    out = nd._slice_assign(nd.array(x), nd.array(v),
                           begin=(0, 1), end=(2, 3)).asnumpy()
    expect = x.copy()
    expect[0:2, 1:3] = 9.0
    assert np.allclose(out, expect)
    out2 = nd._crop_assign_scalar(nd.array(x), begin=(1, 0), end=(3, 2),
                                  scalar=-1.0).asnumpy()
    expect2 = x.copy()
    expect2[1:3, 0:2] = -1.0
    assert np.allclose(out2, expect2)
    # aliases exist
    assert np.allclose(nd._sub(nd.array(x), nd.array(x)).asnumpy(), 0.0)
    assert np.allclose(nd._grad_add(nd.array(x), nd.array(x)).asnumpy(),
                       2 * x)
    assert np.allclose(nd._CrossDeviceCopy(nd.array(x)).asnumpy(), x)


# The eight max-pool geometries of ISSUE 27: (name, attrs, input H x W).
_MAXPOOL_CASES = [
    ('3x3s2p1', {'kernel': (3, 3), 'stride': (2, 2), 'pad': (1, 1)},
     (10, 10)),
    ('3x3s2p0_odd', {'kernel': (3, 3), 'stride': (2, 2)}, (9, 11)),
    ('3x3s2_full', {'kernel': (3, 3), 'stride': (2, 2),
                    'pooling_convention': 'full'}, (9, 10)),
    ('2x2s2', {'kernel': (2, 2), 'stride': (2, 2)}, (9, 8)),
    ('3x3s1p1', {'kernel': (3, 3), 'stride': (1, 1), 'pad': (1, 1)},
     (8, 7)),
    ('3x2s2x1', {'kernel': (3, 2), 'stride': (2, 1)}, (9, 7)),
    ('2x2s3_gaps', {'kernel': (2, 2), 'stride': (3, 3)}, (10, 11)),
    ('5x5s2_full', {'kernel': (5, 5), 'stride': (2, 2), 'pad': (1, 1),
                    'pooling_convention': 'full'}, (12, 11)),
]
_MAXPOOL_PARAMS = [pytest.param(attrs, hw, dtype, id='%s-%s' % (name, dtype))
                   for name, attrs, hw in _MAXPOOL_CASES
                   for dtype in ('float32', 'bfloat16')]


def _maxpool_input(hw, dtype):
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    x = rng.randn(2, 3, *hw).astype(np.float32)
    # force ties: quantize so equal maxima are common (exact in bf16 too)
    return jnp.asarray(np.round(x * 2) / 2, dtype)


def _maxpool_output_grad(shape, dtype, whole):
    """A random output gradient, so a misrouted term shows.  ``whole``
    draws small whole numbers, whose sums over overlapping windows are
    exact in any order and in bfloat16."""
    import jax.numpy as jnp
    rng = np.random.RandomState(1)
    g = rng.randint(-8, 9, shape) if whole else rng.randn(*shape)
    return jnp.asarray(g, dtype)


def _firstmax_pool_reference(attrs, x, g=None):
    """FROZEN copy of the max pooling this repo ran until PR 27
    (``ops/nn.py _max_pool_firstmax``), the reference for what
    ``Pooling`` must compute: the forward as a max tree over ky*kx
    shifted strided views (NaN wins and sticks, like HLO maximum), and
    the backward that routes a window's gradient to its FIRST maximal
    element and sums overlapping windows in float32, one plane a tap.
    Returns the output, and the input's gradient under ``g``."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.nn import _pool_out_dim
    (ky, kx), (sy, sx) = attrs['kernel'], attrs['stride']
    pad = attrs.get('pad', (0, 0))
    pads = []
    for i in range(2):
        out_d = _pool_out_dim(x.shape[2 + i], attrs['kernel'][i], pad[i],
                              attrs['stride'][i],
                              attrs.get('pooling_convention', 'valid'))
        needed = (out_d - 1) * attrs['stride'][i] + attrs['kernel'][i] \
            - x.shape[2 + i]
        pads.append((pad[i], max(needed - pad[i], pad[i])))
    padded = jnp.pad(x, ((0, 0), (0, 0)) + tuple(pads),
                     constant_values=jnp.asarray(-jnp.inf, x.dtype))
    padded_h, padded_w = padded.shape[2:]
    oh = (padded_h - ky) // sy + 1
    ow = (padded_w - kx) // sx + 1
    out = idx = None
    for t in range(ky * kx):
        dy, dx = divmod(t, kx)
        v = jax.lax.slice(
            padded, (0, 0, dy, dx),
            (x.shape[0], x.shape[1], dy + (oh - 1) * sy + 1,
             dx + (ow - 1) * sx + 1), (1, 1, sy, sx))
        if out is None:
            out, idx = v, jnp.zeros(v.shape, jnp.int8)
            continue
        better = (v > out) | (jnp.isnan(v) & ~jnp.isnan(out))
        out = jnp.where(better, v, out)
        idx = jnp.where(better, jnp.int8(t), idx)
    if g is None:
        return np.asarray(out, np.float32), None
    g32 = g.astype(jnp.float32)
    acc = jnp.zeros(x.shape[:2] + (padded_h, padded_w), jnp.float32)
    for t in range(ky * kx):
        dy, dx = divmod(t, kx)
        acc = acc + jax.lax.pad(
            jnp.where(idx == t, g32, 0.0), jnp.float32(0.0),
            ((0, 0, 0), (0, 0, 0),
             (dy, padded_h - dy - ((oh - 1) * sy + 1), sy - 1),
             (dx, padded_w - dx - ((ow - 1) * sx + 1), sx - 1)))
    grad = acc[:, :, pads[0][0]:padded_h - pads[0][1],
               pads[1][0]:padded_w - pads[1][1]].astype(x.dtype)
    return np.asarray(out, np.float32), np.asarray(grad, np.float32)


def _maxpool(attrs):
    from mxnet_tpu.ops.nn import _pooling_apply
    attrs = dict(attrs, pool_type='max')
    return lambda data: _pooling_apply(attrs, [data], True, None)[0][0]


def _maxpool_fwd_and_grad(attrs, x, whole_g):
    import jax
    out, vjp = jax.vjp(_maxpool(attrs), x)
    g = _maxpool_output_grad(out.shape, x.dtype, whole_g)
    return (np.asarray(out, np.float32),
            np.asarray(vjp(g)[0], np.float32), g)


@pytest.mark.parametrize('attrs,hw,dtype', _MAXPOOL_PARAMS)
def test_maxpool_matches_firstmax_reference(attrs, hw, dtype):
    """Max pooling (reduce_window, whose gradient is select_and_scatter)
    against the frozen shifted-view reference: forward AND gradient,
    exactly, including tie windows (on the CPU both route to the FIRST
    maximal element)."""
    x = _maxpool_input(hw, dtype)
    out, grad, g = _maxpool_fwd_and_grad(attrs, x, whole_g=True)
    out_ref, grad_ref = _firstmax_pool_reference(attrs, x, g)
    np.testing.assert_array_equal(out, out_ref)
    assert grad_ref.any()
    np.testing.assert_array_equal(grad, grad_ref)


@pytest.mark.parametrize('attrs,hw,dtype', _MAXPOOL_PARAMS)
def test_maxpool_gradient_sums_overlaps_like_float32(attrs, hw, dtype):
    """Under a real-valued output gradient the sum over overlapping
    windows stays within rounding of the reference's float32 sum: the
    order of a float32 sum may differ, and on the CPU a bfloat16
    select_and_scatter rounds after every term (at most
    ceil(ky/sy)*ceil(kx/sx) of them; on the v5e it rounds once,
    PERF.md section 6, PR 27)."""
    x = _maxpool_input(hw, dtype)
    _, grad, g = _maxpool_fwd_and_grad(attrs, x, whole_g=False)
    _, grad_ref = _firstmax_pool_reference(attrs, x, g)
    if dtype == 'float32':
        np.testing.assert_allclose(grad, grad_ref, rtol=1e-6, atol=1e-6)
    else:
        terms = -(-attrs['kernel'][0] // attrs['stride'][0]) * \
            -(-attrs['kernel'][1] // attrs['stride'][1])
        np.testing.assert_allclose(grad, grad_ref, rtol=terms * 2.0 ** -8,
                                   atol=terms * 2.0 ** -8)


def test_maxpool_forward_propagates_nan():
    """Forward NaN propagation matches HLO maximum semantics (gradient
    routing under NaN is unspecified)."""
    import jax.numpy as jnp
    xn = np.array(_maxpool_input((9, 9), 'float32'))
    xn[0, 0, 4, 4] = np.nan
    attrs = {'kernel': (3, 3), 'stride': (2, 2)}
    out = np.asarray(_maxpool(attrs)(jnp.asarray(xn)))
    out_ref, _ = _firstmax_pool_reference(attrs, jnp.asarray(xn))
    np.testing.assert_allclose(out, out_ref)
    assert np.isnan(out).any()


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else (value,)):
                sub = getattr(sub, 'jaxpr', sub)
                if hasattr(sub, 'eqns'):
                    yield from _walk_eqns(sub)


@pytest.mark.parametrize('kernel', [(3, 3), (7, 7)])
def test_maxpool_backward_is_select_and_scatter(kernel):
    """What made the max-pool backward a fifth of the step on the chip
    cannot come back unseen: one select_and_scatter, no float32 value
    of the padded input's spatial size, no interior padding."""
    import jax
    import jax.numpy as jnp
    pool = _maxpool({'kernel': kernel, 'stride': (2, 2), 'pad': (1, 1)})
    in_shape = (2, 8, 16, 16)

    def bwd(x):
        out, vjp = jax.vjp(pool, x)
        return vjp(jnp.ones_like(out))[0]
    jaxpr = jax.make_jaxpr(bwd)(jnp.zeros(in_shape, jnp.bfloat16))
    assert jaxpr.out_avals[0].shape == in_shape
    assert jaxpr.out_avals[0].dtype == jnp.bfloat16
    eqns = list(_walk_eqns(jaxpr.jaxpr))
    assert [eqn.primitive.name for eqn in eqns].count(
        'select_and_scatter_add') == 1
    for eqn in eqns:
        for var in eqn.outvars:
            assert not (var.aval.dtype == jnp.float32 and
                        tuple(var.aval.shape[-2:]) in ((18, 18), (16, 16))
                        ), eqn
        if eqn.primitive.name == 'pad':
            assert all(interior == 0 for _, _, interior
                       in eqn.params['padding_config']), eqn
