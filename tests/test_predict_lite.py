"""The Python-free predict-lite core (amalgamation/predict_lite.cc):
numerics must match the real (JAX) predictor on the deployment nets,
since lite re-implements every op in plain C++.  Also validates the
JNI wrapper dry-compile and the emcc target's clean skip."""
import ctypes
import os
import subprocess

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import sym, nd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AMALG = os.path.join(ROOT, 'amalgamation')
SO = os.path.join(AMALG, 'libmxtpu_predict_lite.so')


def build_lib():
    # always run make: its dependency tracking rebuilds a stale .so
    subprocess.check_call(['make', '-s', 'lite'], cwd=AMALG)
    L = ctypes.CDLL(SO)
    L.MXGetLastError.restype = ctypes.c_char_p
    return L


def lite_forward(L, sym_json, param_bytes, data):
    keys = (ctypes.c_char_p * 1)(b'data')
    indptr = (ctypes.c_uint * 2)(0, len(data.shape))
    shape = (ctypes.c_uint * len(data.shape))(*data.shape)
    handle = ctypes.c_void_p()
    rc = L.MXPredCreate(sym_json.encode(), param_bytes,
                        len(param_bytes), 1, 0, 1, keys, indptr, shape,
                        ctypes.byref(handle))
    assert rc == 0, L.MXGetLastError()
    xa = np.ascontiguousarray(data, np.float32)
    assert L.MXPredSetInput(
        handle, b'data',
        xa.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        xa.size) == 0, L.MXGetLastError()
    assert L.MXPredForward(handle) == 0, L.MXGetLastError()
    sdata = ctypes.POINTER(ctypes.c_uint)()
    sndim = ctypes.c_uint()
    assert L.MXPredGetOutputShape(handle, 0, ctypes.byref(sdata),
                                  ctypes.byref(sndim)) == 0
    out_shape = tuple(sdata[i] for i in range(sndim.value))
    out = np.zeros(int(np.prod(out_shape)), np.float32)
    assert L.MXPredGetOutput(
        handle, 0, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out.size) == 0, L.MXGetLastError()
    assert L.MXPredFree(handle) == 0
    return out.reshape(out_shape)


def make_blob(net, dshape, seed=0):
    rng = np.random.RandomState(seed)
    arg_shapes, _, aux_shapes = net.infer_shape(data=dshape)
    params = {}
    for name, shape in zip(net.list_arguments(), arg_shapes):
        if name in ('data', 'softmax_label'):
            continue
        params['arg:' + name] = nd.array(
            rng.randn(*shape).astype(np.float32) * 0.2)
    for name, shape in zip(net.list_auxiliary_states(), aux_shapes):
        init = np.abs(rng.randn(*shape)) + 0.5 if 'var' in name \
            else rng.randn(*shape) * 0.1
        params['aux:' + name] = nd.array(init.astype(np.float32))
    import tempfile
    with tempfile.NamedTemporaryFile(suffix='.params') as f:
        nd.save(f.name, params)
        f.seek(0)
        blob = f.read()
    return blob, rng


def reference_forward(net, dshape, blob, data):
    from mxnet_tpu.predictor import Predictor
    pred = Predictor(net.tojson(), blob, {'data': dshape})
    return pred.forward(data=data)[0].asnumpy()


def check_net(net, dshape, seed=0, atol=1e-4):
    L = build_lib()
    blob, rng = make_blob(net, dshape, seed)
    data = rng.rand(*dshape).astype(np.float32)
    got = lite_forward(L, net.tojson(), blob, data)
    want = reference_forward(net, dshape, blob, data)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=atol)


def test_mlp():
    d = sym.Variable('data')
    fc1 = sym.FullyConnected(d, num_hidden=16, name='fc1')
    a = sym.Activation(fc1, act_type='relu')
    fc2 = sym.FullyConnected(a, num_hidden=5, name='fc2')
    check_net(sym.SoftmaxOutput(fc2, name='softmax'), (3, 8))


def test_lenet():
    from mxnet_tpu import models
    net = models.get_symbol('lenet', num_classes=10)
    check_net(net, (2, 1, 28, 28))


def test_small_resnet_block():
    """conv + BN + relu + strided conv + shortcut add + pooling — the
    ResNet building blocks incl. moving-stats BatchNorm."""
    d = sym.Variable('data')
    c1 = sym.Convolution(d, num_filter=8, kernel=(3, 3), pad=(1, 1),
                         no_bias=True, name='c1')
    bn = sym.BatchNorm(c1, fix_gamma=False, name='bn1')
    act = sym.Activation(bn, act_type='relu')
    c2 = sym.Convolution(act, num_filter=8, kernel=(3, 3), pad=(1, 1),
                         no_bias=True, name='c2')
    add = c2 + c1
    pool = sym.Pooling(add, global_pool=True, kernel=(2, 2),
                       pool_type='avg')
    fc = sym.FullyConnected(sym.Flatten(pool), num_hidden=4, name='fc')
    check_net(sym.SoftmaxOutput(fc, name='softmax'), (2, 3, 16, 16))


def test_padded_avg_pool_and_reshape_codes():
    """avg pooling divides by the FULL kernel (padded cells count,
    mshadow semantics) and Reshape honors the 0 copy-dim code."""
    d = sym.Variable('data')
    pool = sym.Pooling(d, kernel=(2, 2), stride=(2, 2), pad=(1, 1),
                       pool_type='avg')
    rs = sym.Reshape(pool, shape=(0, -1))
    fc = sym.FullyConnected(rs, num_hidden=3, name='fc')
    check_net(sym.SoftmaxOutput(fc, name='softmax'), (2, 2, 6, 6))


def test_unsupported_op_reports_cleanly():
    L = build_lib()
    d = sym.Variable('data')
    net = sym.SoftmaxOutput(
        sym.Flatten(sym.UpSampling(d, scale=2, sample_type='nearest',
                                   num_args=1)), name='softmax')
    blob, rng = make_blob(net, (1, 2, 4, 4))
    keys = (ctypes.c_char_p * 1)(b'data')
    indptr = (ctypes.c_uint * 2)(0, 4)
    shape = (ctypes.c_uint * 4)(1, 2, 4, 4)
    handle = ctypes.c_void_p()
    rc = L.MXPredCreate(net.tojson().encode(), blob, len(blob), 1, 0,
                        1, keys, indptr, shape, ctypes.byref(handle))
    assert rc == -1
    assert b'unsupported op' in L.MXGetLastError()


def test_jni_dry_compile_and_js_skip():
    """`make jni` must at least dry-compile the wrapper (full build
    with a JDK); `make js` must skip cleanly without emcc."""
    env = dict(os.environ)
    res = subprocess.run(['make', 'jni'], cwd=AMALG, env=env,
                         capture_output=True, text=True)
    assert res.returncode == 0, res.stdout + res.stderr
    assert ('dry-compiled' in res.stdout
            or os.path.exists(os.path.join(
                AMALG, 'libmxtpu_predict_jni.so'))
            or 'up to date' in res.stdout), res.stdout
    res = subprocess.run(['make', 'js'], cwd=AMALG, env=env,
                         capture_output=True, text=True)
    assert res.returncode == 0, res.stdout + res.stderr
