"""The ABI tail added for full c_api.h name coverage: legacy function
registry (MXFuncInvoke), raw-bytes NDArray serialization, symbol
file/group/attr surfaces, partial shape inference, profiler entries,
and the documented-unsupported stubs."""
import ctypes
import os
import subprocess

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import sym, nd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SO = os.path.join(ROOT, 'mxnet_tpu', 'libmxtpu_predict.so')


def lib():
    # always run make: its dependency tracking rebuilds a stale .so
    subprocess.check_call(['make', '-s', 'predict'],
                          cwd=os.path.join(ROOT, 'src'))
    L = ctypes.CDLL(SO)
    L.MXGetLastError.restype = ctypes.c_char_p
    return L


def make_nd(L, arr):
    arr = np.ascontiguousarray(arr, np.float32)
    shape = (ctypes.c_uint * arr.ndim)(*arr.shape)
    h = ctypes.c_void_p()
    assert L.MXNDArrayCreate(shape, arr.ndim, 1, 0, 0,
                             ctypes.byref(h)) == 0
    assert L.MXNDArraySyncCopyFromCPU(
        h, arr.ctypes.data_as(ctypes.c_void_p),
        ctypes.c_size_t(arr.size)) == 0
    return h


def read_nd(L, h, n):
    out = np.zeros(n, np.float32)
    assert L.MXNDArraySyncCopyToCPU(
        h, out.ctypes.data_as(ctypes.c_void_p), ctypes.c_size_t(n)) == 0
    return out


def test_func_registry_invoke():
    L = lib()
    fun = ctypes.c_void_p()
    assert L.MXGetFunction(b'sgd_update', ctypes.byref(fun)) == 0
    nu = ctypes.c_uint()
    ns = ctypes.c_uint()
    nm = ctypes.c_uint()
    mask = ctypes.c_int()
    assert L.MXFuncDescribe(fun, ctypes.byref(nu), ctypes.byref(ns),
                            ctypes.byref(nm), ctypes.byref(mask)) == 0
    assert nm.value == 1
    w = make_nd(L, np.ones(8))
    g = make_nd(L, np.ones(8))
    # scalars follow arg_order: lr, wd, rescale_grad, clip_gradient
    scalars = (ctypes.c_float * int(ns.value))(
        *([0.5, 0.0, 1.0, -1.0][:ns.value]))
    use = (ctypes.c_void_p * 1)(w)
    mut = (ctypes.c_void_p * 1)(w)
    # w <- w - lr * g = 1 - 0.5 = 0.5  (use var order: weight, grad)
    use2 = (ctypes.c_void_p * 2)(w, g)
    assert L.MXFuncInvoke(fun, use2, scalars, mut) == 0, \
        L.MXGetLastError()
    np.testing.assert_allclose(read_nd(L, w, 8), 0.5, rtol=1e-6)
    L.MXNDArrayFree(w)
    L.MXNDArrayFree(g)


def test_raw_bytes_roundtrip_and_getdata():
    L = lib()
    a = make_nd(L, np.arange(12, dtype=np.float32).reshape(3, 4))
    size = ctypes.c_size_t()
    buf = ctypes.c_char_p()
    assert L.MXNDArraySaveRawBytes(a, ctypes.byref(size),
                                   ctypes.byref(buf)) == 0
    raw = ctypes.string_at(buf, size.value)
    h2 = ctypes.c_void_p()
    assert L.MXNDArrayLoadFromRawBytes(raw, len(raw),
                                       ctypes.byref(h2)) == 0
    np.testing.assert_allclose(read_nd(L, h2, 12),
                               np.arange(12, dtype=np.float32))
    # host-snapshot data pointer
    p = ctypes.c_void_p()
    assert L.MXNDArrayGetData(a, ctypes.byref(p)) == 0
    snap = np.ctypeslib.as_array(
        ctypes.cast(p, ctypes.POINTER(ctypes.c_float)), shape=(12,))
    np.testing.assert_allclose(snap, np.arange(12, dtype=np.float32))
    L.MXNDArrayFree(a)
    L.MXNDArrayFree(h2)


def test_symbol_file_group_attrs(tmp_path):
    L = lib()
    d = sym.Variable('data')
    fc = sym.FullyConnected(d, num_hidden=4, name='fc1')
    net = sym.SoftmaxOutput(fc, name='softmax')
    path = str(tmp_path / 'net.json')
    with open(path, 'w') as f:
        f.write(net.tojson())

    h = ctypes.c_void_p()
    assert L.MXSymbolCreateFromFile(path.encode(),
                                    ctypes.byref(h)) == 0
    name = ctypes.c_char_p()
    ok = ctypes.c_int()
    assert L.MXSymbolGetName(h, ctypes.byref(name),
                             ctypes.byref(ok)) == 0
    assert ok.value == 1 and name.value == b'softmax'

    assert L.MXSymbolSetAttr(h, b'__layout__', b'NCHW') == 0
    val = ctypes.c_char_p()
    assert L.MXSymbolGetAttr(h, b'__layout__', ctypes.byref(val),
                             ctypes.byref(ok)) == 0
    assert ok.value == 1 and val.value == b'NCHW'
    n_pairs = ctypes.c_uint()
    arr = ctypes.POINTER(ctypes.c_char_p)()
    assert L.MXSymbolListAttrShallow(h, ctypes.byref(n_pairs),
                                     ctypes.byref(arr)) == 0
    pairs = {arr[2 * i]: arr[2 * i + 1]
             for i in range(n_pairs.value)}
    assert pairs.get(b'__layout__') == b'NCHW'

    # children of the softmax head: the fc output + label variable
    child = ctypes.c_void_p()
    assert L.MXSymbolGetChildren(h, ctypes.byref(child)) == 0
    n_out = ctypes.c_uint()
    outs = ctypes.POINTER(ctypes.c_char_p)()
    assert L.MXSymbolListOutputs(child, ctypes.byref(n_out),
                                 ctypes.byref(outs)) == 0
    assert n_out.value == 2

    # save to file round-trips
    path2 = str(tmp_path / 'net2.json')
    assert L.MXSymbolSaveToFile(h, path2.encode()) == 0
    h2 = ctypes.c_void_p()
    assert L.MXSymbolCreateFromFile(path2.encode(),
                                    ctypes.byref(h2)) == 0

    # group of two symbols has 2 outputs
    grp = ctypes.c_void_p()
    two = (ctypes.c_void_p * 2)(h, h2)
    assert L.MXSymbolCreateGroup(2, two, ctypes.byref(grp)) == 0
    assert L.MXSymbolListOutputs(grp, ctypes.byref(n_out),
                                 ctypes.byref(outs)) == 0
    assert n_out.value == 2

    # partial inference with nothing known: rc 0, complete 0
    indptr = (ctypes.c_uint * 1)(0)
    in_n = ctypes.c_uint()
    out_n = ctypes.c_uint()
    aux_n = ctypes.c_uint()
    in_nd = ctypes.POINTER(ctypes.c_uint)()
    out_nd = ctypes.POINTER(ctypes.c_uint)()
    aux_nd = ctypes.POINTER(ctypes.c_uint)()
    in_s = ctypes.POINTER(ctypes.POINTER(ctypes.c_uint))()
    out_s = ctypes.POINTER(ctypes.POINTER(ctypes.c_uint))()
    aux_s = ctypes.POINTER(ctypes.POINTER(ctypes.c_uint))()
    complete = ctypes.c_int()
    assert L.MXSymbolInferShapePartial(
        h, 0, None, indptr, None, ctypes.byref(in_n),
        ctypes.byref(in_nd), ctypes.byref(in_s), ctypes.byref(out_n),
        ctypes.byref(out_nd), ctypes.byref(out_s), ctypes.byref(aux_n),
        ctypes.byref(aux_nd), ctypes.byref(aux_s),
        ctypes.byref(complete)) == 0
    assert complete.value == 0


def test_profiler_and_unsupported_stubs(tmp_path):
    L = lib()
    prof = str(tmp_path / 'profile.json')
    assert L.MXSetProfilerConfig(0, prof.encode()) == 0
    assert L.MXSetProfilerState(1) == 0
    assert L.MXSetProfilerState(0) == 0
    assert L.MXDumpProfile() == 0
    assert L.MXInitPSEnv(1, (ctypes.c_char_p * 1)(b'DMLC_ROLE'),
                         (ctypes.c_char_p * 1)(b'worker')) == 0
    assert os.environ.get('DMLC_ROLE') == 'worker'
    # documented-unsupported entries fail CLEANLY with a message
    out = ctypes.c_void_p()
    assert L.MXSymbolGrad(None, 0, None, ctypes.byref(out)) == -1
    assert b'MXExecutorBackward' in L.MXGetLastError()
    assert L.MXCustomOpRegister(b'x', None) == -1
    assert b'register custom ops from Python' in L.MXGetLastError()
