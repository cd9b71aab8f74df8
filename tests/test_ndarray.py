"""NDArray tests (reference tests/python/unittest/test_ndarray.py)."""
import os
import tempfile

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd


def test_creation():
    a = nd.zeros((3, 4))
    assert a.shape == (3, 4)
    assert a.asnumpy().sum() == 0
    b = nd.ones((2, 2))
    assert b.asnumpy().sum() == 4
    c = nd.full((2, 2), 3.5)
    assert np.allclose(c.asnumpy(), 3.5)
    d = nd.array([[1, 2], [3, 4]])
    assert d.dtype == np.float32
    e = nd.arange(0, 10, 2)
    assert np.allclose(e.asnumpy(), [0, 2, 4, 6, 8])


def test_arithmetic():
    a = nd.array([[1.0, 2.0], [3.0, 4.0]])
    b = nd.array([[2.0, 2.0], [2.0, 2.0]])
    assert np.allclose((a + b).asnumpy(), [[3, 4], [5, 6]])
    assert np.allclose((a - b).asnumpy(), [[-1, 0], [1, 2]])
    assert np.allclose((a * b).asnumpy(), [[2, 4], [6, 8]])
    assert np.allclose((a / b).asnumpy(), [[0.5, 1], [1.5, 2]])
    assert np.allclose((a ** 2).asnumpy(), [[1, 4], [9, 16]])
    assert np.allclose((2 + a).asnumpy(), [[3, 4], [5, 6]])
    assert np.allclose((2 - a).asnumpy(), [[1, 0], [-1, -2]])
    assert np.allclose((2 / a).asnumpy(), [[2, 1], [2 / 3, 0.5]])
    assert np.allclose((-a).asnumpy(), [[-1, -2], [-3, -4]])


def test_inplace():
    a = nd.ones((2, 2))
    b = a
    a += 1
    assert np.allclose(b.asnumpy(), 2)
    a *= 3
    assert np.allclose(b.asnumpy(), 6)
    a /= 2
    assert np.allclose(b.asnumpy(), 3)
    a -= 1
    assert np.allclose(b.asnumpy(), 2)


def test_setitem_getitem():
    a = nd.zeros((4, 4))
    a[:] = 2.0
    assert np.allclose(a.asnumpy(), 2.0)
    a[1] = 5.0
    npy = a.asnumpy()
    assert np.allclose(npy[1], 5.0)
    assert np.allclose(npy[0], 2.0)
    b = a[1]
    assert b.shape == (4,)
    c = a[1:3]
    assert c.shape == (2, 4)
    a[:] = np.arange(16).reshape(4, 4)
    assert np.allclose(a[2:4].asnumpy(), np.arange(16).reshape(4, 4)[2:4])


def test_imperative_ops():
    a = nd.array([[-1.0, 2.0], [3.0, -4.0]])
    assert np.allclose(nd.relu(a).asnumpy(), [[0, 2], [3, 0]])
    assert np.allclose(nd.abs(a).asnumpy(), [[1, 2], [3, 4]])
    assert np.allclose(nd.sum(a).asnumpy(), 0.0)
    assert np.allclose(nd.sum(a, axis=1).asnumpy(), [1.0, -1.0])
    assert np.allclose(nd.max(a).asnumpy(), 3.0)
    assert np.allclose(nd.transpose(a).asnumpy(), a.asnumpy().T)
    x = nd.array(np.random.randn(3, 4))
    y = nd.array(np.random.randn(4, 5))
    assert np.allclose(nd.dot(x, y).asnumpy(),
                       x.asnumpy() @ y.asnumpy(), atol=1e-5)


def test_reshape_slice():
    a = nd.arange(0, 24).reshape((2, 3, 4))
    assert a.shape == (2, 3, 4)
    b = nd.Reshape(a, shape=(6, 4))
    assert b.shape == (6, 4)
    c = nd.slice_axis(a, axis=2, begin=1, end=3)
    assert c.shape == (2, 3, 2)
    d = nd.Flatten(a)
    assert d.shape == (2, 12)


def test_broadcast():
    a = nd.ones((2, 1, 3))
    b = nd.broadcast_to(a, shape=(2, 4, 3))
    assert b.shape == (2, 4, 3)
    x = nd.array(np.random.rand(2, 3))
    y = nd.array(np.random.rand(1, 3))
    z = nd.broadcast_add(x, y)
    assert np.allclose(z.asnumpy(), x.asnumpy() + y.asnumpy())


def test_default_context_follows_the_backend():
    """With no context set the default follows JAX's default backend
    (docs/deviations.md): cpu(0) in this CPU-pinned process, and
    whatever a ``with`` block names inside it."""
    assert mx.current_context() == mx.cpu(0)
    assert mx.nd.zeros((2,)).context == mx.cpu(0)
    with mx.tpu(1):
        assert mx.current_context() == mx.tpu(1)
    assert mx.current_context() == mx.cpu(0)


def test_tpu_context_resolution():
    """tpu(i) is device i of the default backend, which is the CPU only
    because this process is pinned to it; an id past the device count
    is an error, never a wrapped id."""
    import jax
    devs = jax.local_devices()
    assert mx.tpu(len(devs) - 1).jax_device == devs[-1]
    with pytest.raises(mx.MXNetError, match='out of range'):
        mx.tpu(len(devs)).jax_device
    with pytest.raises(mx.MXNetError, match='out of range'):
        mx.nd.zeros((2,), ctx=mx.tpu(len(devs)))
    # cpu ids name the same host, as in the reference
    assert mx.cpu(len(devs)).jax_device == devs[0]


def test_tpu_context_needs_an_accelerator_unless_pinned(monkeypatch):
    """Where JAX fell back to the CPU by itself (no accelerator found,
    platform not pinned), tpu() raises instead of using the host."""
    from mxnet_tpu import context
    monkeypatch.setattr(context, '_pinned_to_cpu', lambda: False)
    with pytest.raises(mx.MXNetError, match='no accelerator'):
        mx.tpu(0).jax_device


def test_copyto_context():
    a = nd.ones((2, 2), ctx=mx.cpu())
    b = a.copyto(mx.tpu(0))
    assert np.allclose(b.asnumpy(), 1.0)
    c = a.as_in_context(mx.cpu())
    assert c is a
    d = nd.zeros((2, 2))
    a.copyto(d)
    assert np.allclose(d.asnumpy(), 1.0)


def test_save_load():
    with tempfile.TemporaryDirectory() as tmp:
        fname = os.path.join(tmp, 'nd.bin')
        a = nd.array(np.random.rand(3, 4))
        b = nd.array(np.random.rand(5,))
        nd.save(fname, [a, b])
        loaded = nd.load(fname)
        assert len(loaded) == 2
        assert np.allclose(loaded[0].asnumpy(), a.asnumpy())
        assert np.allclose(loaded[1].asnumpy(), b.asnumpy())
        nd.save(fname, {'a': a, 'b': b})
        loaded = nd.load(fname)
        assert set(loaded.keys()) == {'a', 'b'}
        assert np.allclose(loaded['a'].asnumpy(), a.asnumpy())


def test_pickle():
    import pickle
    a = nd.array(np.random.rand(3, 3))
    data = pickle.dumps(a)
    b = pickle.loads(data)
    assert np.allclose(a.asnumpy(), b.asnumpy())


def test_dtype():
    a = nd.zeros((2, 2), dtype='float16')
    assert a.dtype == np.float16
    b = a.astype('float32')
    assert b.dtype == np.float32
    c = nd.zeros((2, 2), dtype='bfloat16')
    assert 'bfloat16' in str(c.dtype)


def test_wait_and_sync():
    a = nd.ones((100, 100))
    b = nd.dot(a, a)
    b.wait_to_read()
    nd.waitall()
    assert np.allclose(b.asnumpy()[0, 0], 100.0)


def test_topk_sort():
    a = nd.array([[3.0, 1.0, 2.0], [0.0, 5.0, 4.0]])
    idx = nd.topk(a, k=2)
    assert np.allclose(idx.asnumpy(), [[0, 2], [1, 2]])
    vals = nd.topk(a, k=1, ret_typ='value')
    assert np.allclose(vals.asnumpy(), [[3.0], [5.0]])
    s = nd.sort(a)
    assert np.allclose(s.asnumpy(), np.sort(a.asnumpy(), axis=-1))
    asort = nd.argsort(a)
    assert np.allclose(asort.asnumpy(),
                       np.argsort(a.asnumpy(), axis=-1))


def test_onehot():
    idx = nd.array([0.0, 2.0])
    out = nd.one_hot(idx, depth=3)
    assert np.allclose(out.asnumpy(), [[1, 0, 0], [0, 0, 1]])
