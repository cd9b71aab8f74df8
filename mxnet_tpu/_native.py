"""ctypes binding to the native IO runtime (src/recordio.cc).

The reference crosses this boundary via the C API
(``MXRecordIOReaderCreate`` etc., ``src/c_api/c_api.cc:720-805``); here
the flat ABI is loaded directly with ctypes.  The shared object is built
on first use with g++ (no pip deps), and rebuilt when any of its
``src/*.cc`` is newer than it, so what is loaded always corresponds to
the sources in the tree.
"""
from __future__ import annotations

import ctypes
import os
import subprocess

_LIB = None


_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, '..', 'src')


def _fresh_so(so_path, sources, extra_link):
    """``so_path``, (re)built from ``sources`` unless it is at least as
    new as every one of them: the same mtime rule as ``src/Makefile``,
    so a library older than its sources (left over from another commit)
    is never loaded.  Compiles to a per-pid temp file, then os.rename
    into place — rename is atomic on POSIX, so concurrent builders
    (forked dist workers, parallel test runners) never load a
    half-written .so."""
    sources = list(sources)
    if os.path.exists(so_path) and os.path.getmtime(so_path) >= \
            max(os.path.getmtime(s) for s in sources):
        return so_path
    tmp = '%s.%d.tmp' % (so_path, os.getpid())
    subprocess.check_call(
        ['g++', '-O3', '-std=c++17', '-fPIC', '-Wall', '-shared'] +
        sources + ['-o', tmp] + list(extra_link))
    os.rename(tmp, so_path)
    return so_path


def lib():
    global _LIB
    if _LIB is not None:
        return _LIB
    L = ctypes.CDLL(_fresh_so(
        os.path.join(_HERE, 'libmxtpu_io_abi2.so'),
        [os.path.join(_SRC, 'recordio.cc')], ['-ljpeg', '-lpthread']))
    L.MXTPURecordIOWriterCreate.restype = ctypes.c_void_p
    L.MXTPURecordIOWriterCreate.argtypes = [ctypes.c_char_p]
    L.MXTPURecordIOWriterTell.restype = ctypes.c_long
    L.MXTPURecordIOWriterTell.argtypes = [ctypes.c_void_p]
    L.MXTPURecordIOWriterWrite.restype = ctypes.c_int
    L.MXTPURecordIOWriterWrite.argtypes = [ctypes.c_void_p,
                                           ctypes.c_char_p,
                                           ctypes.c_size_t]
    L.MXTPURecordIOWriterFree.argtypes = [ctypes.c_void_p]
    L.MXTPURecordIOReaderCreate.restype = ctypes.c_void_p
    L.MXTPURecordIOReaderCreate.argtypes = [ctypes.c_char_p]
    L.MXTPURecordIOReaderNext.restype = ctypes.POINTER(ctypes.c_char)
    L.MXTPURecordIOReaderNext.argtypes = [ctypes.c_void_p,
                                          ctypes.POINTER(ctypes.c_size_t)]
    L.MXTPURecordIOReaderSeek.argtypes = [ctypes.c_void_p, ctypes.c_long]
    L.MXTPURecordIOReaderTell.restype = ctypes.c_long
    L.MXTPURecordIOReaderTell.argtypes = [ctypes.c_void_p]
    L.MXTPURecordIOReaderFree.argtypes = [ctypes.c_void_p]
    L.MXTPUDecodeBatch.restype = ctypes.c_int
    L.MXTPUDecodeBatch.argtypes = [
        ctypes.POINTER(ctypes.c_void_p),            # jpegs
        ctypes.POINTER(ctypes.c_size_t),            # sizes
        ctypes.c_int,                               # n
        ctypes.POINTER(ctypes.c_float),             # out
        ctypes.c_int, ctypes.c_int,                 # out_h, out_w
        ctypes.c_int, ctypes.c_int,                 # rand_crop, rand_mirror
        ctypes.c_float, ctypes.c_float, ctypes.c_float,  # mean rgb
        ctypes.c_float, ctypes.c_float, ctypes.c_float,  # std rgb
        ctypes.c_float, ctypes.c_float,             # max/min random scale
        ctypes.c_uint64, ctypes.c_int]              # seed, nthreads
    L.MXTPUDecodeBatchEx.restype = ctypes.c_int
    L.MXTPUDecodeBatchEx.argtypes = [
        ctypes.POINTER(ctypes.c_void_p),            # jpegs
        ctypes.POINTER(ctypes.c_size_t),            # sizes
        ctypes.c_int,                               # n
        ctypes.POINTER(ctypes.c_float),             # out
        ctypes.c_int, ctypes.c_int,                 # out_h, out_w
        ctypes.c_int, ctypes.c_int,                 # rand_crop, rand_mirror
        ctypes.c_float, ctypes.c_float, ctypes.c_float,  # mean rgb
        ctypes.c_float, ctypes.c_float, ctypes.c_float,  # std rgb
        ctypes.c_float, ctypes.c_float,             # max/min random scale
        ctypes.c_float, ctypes.c_float,    # max_rotate_angle, shear
        ctypes.c_float,                    # max_aspect_ratio
        ctypes.c_int, ctypes.c_int,        # min/max_crop_size
        ctypes.c_float, ctypes.c_float, ctypes.c_float,  # random h/s/l
        ctypes.c_uint64, ctypes.c_int]              # seed, nthreads
    _LIB = L
    return L


_RT_LIB = None

# Python-side callback trampoline type for the native engine.
ENGINE_CALLBACK = ctypes.CFUNCTYPE(None, ctypes.c_void_p)


def rt_lib():
    """Load (building on first use) the native runtime library:
    dependency engine (src/engine.cc) + pooled storage (src/storage.cc)."""
    global _RT_LIB
    if _RT_LIB is not None:
        return _RT_LIB
    L = ctypes.CDLL(_fresh_so(
        os.path.join(_HERE, 'libmxtpu_rt.so'),
        [os.path.join(_SRC, 'engine.cc'), os.path.join(_SRC, 'storage.cc')],
        ['-lpthread']))
    L.MXTPUEngineCreate.restype = ctypes.c_void_p
    L.MXTPUEngineCreate.argtypes = [ctypes.c_int, ctypes.c_int]
    L.MXTPUEngineFree.argtypes = [ctypes.c_void_p]
    L.MXTPUEngineNewVar.restype = ctypes.c_void_p
    L.MXTPUEngineNewVar.argtypes = [ctypes.c_void_p]
    L.MXTPUEngineDelVar.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    L.MXTPUEngineVarVersion.restype = ctypes.c_uint64
    L.MXTPUEngineVarVersion.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    L.MXTPUEnginePushAsync.argtypes = [
        ctypes.c_void_p, ENGINE_CALLBACK, ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_void_p), ctypes.c_int,
        ctypes.POINTER(ctypes.c_void_p), ctypes.c_int,
        ctypes.c_int, ctypes.c_char_p]
    L.MXTPUEngineWaitForVar.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    L.MXTPUEngineWaitForAll.argtypes = [ctypes.c_void_p]
    L.MXTPUEngineSetProfiling.argtypes = [ctypes.c_void_p, ctypes.c_int]
    L.MXTPUEngineDumpProfile.restype = ctypes.c_int
    L.MXTPUEngineDumpProfile.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    L.MXTPUStorageAlloc.restype = ctypes.c_void_p
    L.MXTPUStorageAlloc.argtypes = [ctypes.c_size_t]
    L.MXTPUStorageFree.argtypes = [ctypes.c_void_p]
    L.MXTPUStorageDirectFree.argtypes = [ctypes.c_void_p]
    L.MXTPUStoragePooledBytes.restype = ctypes.c_size_t
    L.MXTPUStorageLiveBytes.restype = ctypes.c_size_t
    L.MXTPUStorageSetPoolCap.argtypes = [ctypes.c_size_t]
    _RT_LIB = L
    return L
