"""MATLAB binding smoke validation without MATLAB/Octave (neither is
in the image): a scripted loader mock that

1. parses ``matlab/+mxnet/mxtpu_predict_proto.m`` (the loadlibrary
   prototype) and checks every declared entry point exists in
   libmxtpu_predict.so with a callable symbol;
2. replays ``matlab/+mxnet/model.m``'s exact call sequence through
   ctypes — including MATLAB's column-major semantics for the image
   path (permute([2 1 3]) + A(:) linearization) and the fliplr-reshape
   of the output — and checks the result against the Python Predictor
   on the equivalent NCHW input.

This is the executable contract for the .m files until a real MATLAB
runs them (reference ``matlab/+mxnet/model.m`` is the surface model)."""
import ctypes
import os
import re
import subprocess

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import sym, nd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SO = os.path.join(ROOT, 'mxnet_tpu', 'libmxtpu_predict.so')
PROTO = os.path.join(ROOT, 'matlab', '+mxnet', 'mxtpu_predict_proto.m')
MODEL_M = os.path.join(ROOT, 'matlab', '+mxnet', 'model.m')


def build_lib():
    # always run make: its dependency tracking rebuilds a stale .so
    subprocess.check_call(['make', '-s', 'predict'],
                          cwd=os.path.join(ROOT, 'src'))
    L = ctypes.CDLL(SO)
    L.MXGetLastError.restype = ctypes.c_char_p
    return L


def declared_functions():
    text = open(PROTO).read()
    return re.findall(r"add\('(\w+)'", text)


def test_proto_matches_library_exports():
    L = build_lib()
    names = declared_functions()
    assert 'MXPredCreate' in names and 'MXPredFree' in names
    for name in names:
        assert hasattr(L, name), 'proto declares %s, .so lacks it' % name


def test_model_m_uses_only_declared_functions():
    declared = set(declared_functions())
    used = set(re.findall(r"calllib\('libmxtpu_predict',\s*'(\w+)'",
                          open(MODEL_M).read()))
    missing = used - declared
    assert not missing, 'model.m calls undeclared: %s' % missing


def _matlab_image_to_c_buffer(img_hwc):
    """What model.m does to an HxWxC image: permute([2 1 3]) then
    A(:) (column-major linearization), shape [1 C H W]."""
    p = np.transpose(img_hwc, (1, 0, 2))       # (W,H,C)
    flat = p.flatten(order='F')                # col-major walk
    h, w, c = img_hwc.shape
    return flat.astype(np.float32), (1, c, h, w)


def test_forward_call_sequence_matches_python_predictor(tmp_path):
    L = build_lib()
    rng = np.random.RandomState(0)
    d = sym.Variable('data')
    c1 = sym.Convolution(d, num_filter=4, kernel=(3, 3), pad=(1, 1),
                         name='c1')
    act = sym.Activation(c1, act_type='relu')
    fc = sym.FullyConnected(sym.Flatten(act), num_hidden=3, name='fc')
    net = sym.SoftmaxOutput(fc, name='softmax')
    params = {}
    for name, shape in zip(net.list_arguments(),
                           net.infer_shape(data=(1, 3, 8, 8))[0]):
        if name in ('data', 'softmax_label'):
            continue
        params['arg:' + name] = nd.array(
            rng.randn(*shape).astype(np.float32) * 0.2)
    pfile = str(tmp_path / 'm.params')
    nd.save(pfile, params)
    blob = open(pfile, 'rb').read()

    img = rng.rand(8, 8, 3).astype(np.float32)     # MATLAB HxWxC image
    data, shape = _matlab_image_to_c_buffer(img)

    # the exact model.m sequence
    keys = (ctypes.c_char_p * 1)(b'data')
    ind = (ctypes.c_uint * 2)(0, 4)
    sdata = (ctypes.c_uint * 4)(*shape)
    hnd = ctypes.c_void_p()
    assert L.MXPredCreate(net.tojson().encode(), blob, len(blob), 1, 0,
                          1, keys, ind, sdata,
                          ctypes.byref(hnd)) == 0, L.MXGetLastError()
    assert L.MXPredSetInput(
        hnd, b'data',
        data.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        data.size) == 0, L.MXGetLastError()
    assert L.MXPredForward(hnd) == 0, L.MXGetLastError()
    sptr = ctypes.POINTER(ctypes.c_uint)()
    nptr = ctypes.c_uint()
    assert L.MXPredGetOutputShape(hnd, 0, ctypes.byref(sptr),
                                  ctypes.byref(nptr)) == 0
    oshape = tuple(sptr[i] for i in range(nptr.value))
    n = int(np.prod(oshape))
    obuf = np.zeros(n, np.float32)
    assert L.MXPredGetOutput(
        hnd, 0, obuf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        n) == 0
    assert L.MXPredFree(hnd) == 0
    # model.m: reshape(obuf, fliplr(oshape)) in column-major = the raw
    # row-major buffer read back transposed; compare the flat values
    from mxnet_tpu.predictor import Predictor
    nchw = np.transpose(img, (2, 0, 1))[None]     # what MATLAB encoded
    np.testing.assert_allclose(
        data.reshape(shape), nchw, rtol=0, atol=0,
        err_msg='MATLAB column-major encoding does not produce NCHW')
    want = Predictor(net.tojson(), blob,
                     {'data': shape}).forward(data=nchw)[0].asnumpy()
    np.testing.assert_allclose(obuf.reshape(oshape), want, rtol=1e-5,
                               atol=1e-6)
