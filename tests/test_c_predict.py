"""C prediction ABI (src/c_predict.cc — the c_predict_api.h equivalent):
drive the flat C interface through ctypes exactly as a C deployment
would, and check parity with the Python Predictor."""
import ctypes
import os
import subprocess

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import sym, nd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SO = os.path.join(ROOT, 'mxnet_tpu', 'libmxtpu_predict.so')
SO_AMALG = os.path.join(ROOT, 'amalgamation',
                        'libmxtpu_predict_amalg.so')


def build_lib(so=SO):
    # always run make: its dependency tracking rebuilds a stale .so
    if so is SO_AMALG:
        subprocess.check_call(['make', '-s'],
                              cwd=os.path.join(ROOT, 'amalgamation'))
    else:
        subprocess.check_call(['make', '-s', 'predict'],
                              cwd=os.path.join(ROOT, 'src'))
    L = ctypes.CDLL(so)
    L.MXGetLastError.restype = ctypes.c_char_p
    L.MXPredCreate.argtypes = [
        ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_uint, ctypes.POINTER(ctypes.c_char_p),
        ctypes.POINTER(ctypes.c_uint), ctypes.POINTER(ctypes.c_uint),
        ctypes.POINTER(ctypes.c_void_p)]
    return L


def make_checkpoint(tmp_path):
    rng = np.random.RandomState(0)
    data = sym.Variable('data')
    fc1 = sym.FullyConnected(data, num_hidden=8, name='fc1')
    act = sym.Activation(fc1, act_type='relu')
    fc2 = sym.FullyConnected(act, num_hidden=3, name='fc2')
    net = sym.SoftmaxOutput(fc2, name='softmax')
    params = {}
    for name, shape in zip(net.list_arguments(),
                           net.infer_shape(data=(2, 6))[0]):
        if name in ('data', 'softmax_label'):
            continue
        params['arg:' + name] = nd.array(
            rng.randn(*shape).astype(np.float32) * 0.2)
    pfile = str(tmp_path / 'model.params')
    nd.save(pfile, params)
    with open(pfile, 'rb') as f:
        param_bytes = f.read()
    return net.tojson(), param_bytes


import pytest


@pytest.mark.parametrize('so', [SO, SO_AMALG],
                         ids=['multifile', 'amalgamation'])
def test_c_predict_end_to_end(tmp_path, so):
    L = build_lib(so)
    sym_json, param_bytes = make_checkpoint(tmp_path)
    keys = (ctypes.c_char_p * 1)(b'data')
    indptr = (ctypes.c_uint * 2)(0, 2)
    shape = (ctypes.c_uint * 2)(2, 6)
    handle = ctypes.c_void_p()
    rc = L.MXPredCreate(sym_json.encode(), param_bytes, len(param_bytes),
                        1, 0, 1, keys, indptr, shape,
                        ctypes.byref(handle))
    assert rc == 0, L.MXGetLastError()

    sdata = ctypes.POINTER(ctypes.c_uint)()
    sndim = ctypes.c_uint()
    assert L.MXPredGetOutputShape(handle, 0, ctypes.byref(sdata),
                                  ctypes.byref(sndim)) == 0
    out_shape = tuple(sdata[i] for i in range(sndim.value))
    assert out_shape == (2, 3)

    rng = np.random.RandomState(1)
    x = rng.randn(2, 6).astype(np.float32)
    xa = np.ascontiguousarray(x)
    assert L.MXPredSetInput(
        handle, b'data',
        xa.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), xa.size) == 0
    assert L.MXPredForward(handle) == 0
    out = np.zeros(6, np.float32)
    assert L.MXPredGetOutput(
        handle, 0, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out.size) == 0

    # parity with the python-level predictor
    from mxnet_tpu.predictor import Predictor
    pred = Predictor(sym_json, param_bytes, {'data': (2, 6)})
    ref = pred.forward(data=x)[0].asnumpy().ravel()
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(out.reshape(2, 3).sum(axis=1), 1.0,
                               atol=1e-4)
    assert L.MXPredFree(handle) == 0


def test_c_predict_bad_input_reports_error(tmp_path):
    L = build_lib()
    sym_json, param_bytes = make_checkpoint(tmp_path)
    keys = (ctypes.c_char_p * 1)(b'data')
    indptr = (ctypes.c_uint * 2)(0, 2)
    shape = (ctypes.c_uint * 2)(2, 6)
    handle = ctypes.c_void_p()
    assert L.MXPredCreate(sym_json.encode(), param_bytes,
                          len(param_bytes), 1, 0, 1, keys, indptr, shape,
                          ctypes.byref(handle)) == 0
    buf = np.zeros(4, np.float32)
    rc = L.MXPredSetInput(
        handle, b'nonexistent',
        buf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), buf.size)
    assert rc == -1
    assert b'nonexistent' in L.MXGetLastError()
    L.MXPredFree(handle)


def test_ndlist_roundtrip(tmp_path):
    L = build_lib()
    mean = nd.array(np.arange(12, dtype=np.float32).reshape(3, 4))
    pfile = str(tmp_path / 'mean.nd')
    nd.save(pfile, {'mean_img': mean})
    with open(pfile, 'rb') as f:
        blob = f.read()
    handle = ctypes.c_void_p()
    length = ctypes.c_uint()
    assert L.MXNDListCreate(blob, len(blob), ctypes.byref(handle),
                            ctypes.byref(length)) == 0
    assert length.value == 1
    key = ctypes.c_char_p()
    data = ctypes.POINTER(ctypes.c_float)()
    shp = ctypes.POINTER(ctypes.c_uint)()
    ndim = ctypes.c_uint()
    assert L.MXNDListGet(handle, 0, ctypes.byref(key), ctypes.byref(data),
                         ctypes.byref(shp), ctypes.byref(ndim)) == 0
    assert key.value == b'mean_img'
    assert tuple(shp[i] for i in range(ndim.value)) == (3, 4)
    vals = np.ctypeslib.as_array(data, shape=(12,))
    np.testing.assert_allclose(vals, np.arange(12, dtype=np.float32))
    assert L.MXNDListFree(handle) == 0
