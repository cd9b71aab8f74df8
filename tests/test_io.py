"""Data iterator tests (reference tests/python/unittest/test_io.py)."""
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import nd


def test_ndarray_iter_basic():
    data = np.arange(100).reshape(25, 4).astype(np.float32)
    labels = np.arange(25).astype(np.float32)
    it = mx.io.NDArrayIter(data, labels, batch_size=5)
    batches = list(it)
    assert len(batches) == 5
    assert batches[0].data[0].shape == (5, 4)
    assert np.allclose(batches[0].data[0].asnumpy(), data[:5])
    assert np.allclose(batches[0].label[0].asnumpy(), labels[:5])
    # reset and re-iterate
    it.reset()
    batches2 = list(it)
    assert len(batches2) == 5


def test_ndarray_iter_pad():
    data = np.arange(28).reshape(7, 4).astype(np.float32)
    it = mx.io.NDArrayIter(data, np.arange(7), batch_size=5,
                           last_batch_handle='pad')
    batches = list(it)
    assert len(batches) == 2
    assert batches[1].pad == 3
    # padded entries wrap around to the beginning
    assert np.allclose(batches[1].data[0].asnumpy()[2:], data[:3])


def test_ndarray_iter_discard():
    data = np.zeros((7, 2), np.float32)
    it = mx.io.NDArrayIter(data, np.zeros(7), batch_size=5,
                           last_batch_handle='discard')
    assert len(list(it)) == 1


def test_ndarray_iter_dict_data():
    data = {'a': np.zeros((10, 2), np.float32),
            'b': np.zeros((10, 3), np.float32)}
    it = mx.io.NDArrayIter(data, np.zeros(10), batch_size=5)
    assert sorted(n for n, _ in it.provide_data) == ['a', 'b']
    b = next(iter(it))
    assert len(b.data) == 2


def test_resize_iter():
    data = np.zeros((20, 2), np.float32)
    base = mx.io.NDArrayIter(data, np.zeros(20), batch_size=5)
    resized = mx.io.ResizeIter(base, 2)
    assert len(list(resized)) == 2
    resized.reset()
    assert len(list(resized)) == 2


def test_prefetching_iter():
    data = np.random.rand(20, 4).astype(np.float32)
    base = mx.io.NDArrayIter(data, np.zeros(20), batch_size=5)
    pre = mx.io.PrefetchingIter(base)
    batches = list(pre)
    assert len(batches) == 4
    assert batches[0].data[0].shape == (5, 4)
    pre.reset()
    assert len(list(pre)) == 4


def test_csv_iter(tmp_path):
    data = np.random.rand(10, 3).astype(np.float32)
    labels = np.arange(10).astype(np.float32)
    dcsv = str(tmp_path / 'data.csv')
    lcsv = str(tmp_path / 'label.csv')
    np.savetxt(dcsv, data, delimiter=',')
    np.savetxt(lcsv, labels, delimiter=',')
    it = mx.io.CSVIter(data_csv=dcsv, data_shape=(3,), label_csv=lcsv,
                       batch_size=5)
    batches = list(it)
    assert len(batches) == 2
    assert np.allclose(batches[0].data[0].asnumpy(), data[:5], atol=1e-5)


def test_bucket_sentence_iter():
    sentences = [[1, 2, 3], [2, 3], [1, 2, 3, 4, 5, 6, 7],
                 [3, 2, 1], [1, 1]] * 4
    it = mx.rnn.BucketSentenceIter(sentences, batch_size=2, buckets=[4, 8])
    batch = next(it)
    assert batch.bucket_key in (4, 8)
    assert batch.data[0].shape[0] == 2
    it.reset()
    count = sum(1 for _ in it)
    assert count >= 4


def test_prefetch_multi_iter_error_aborts_epoch():
    """With multiple iterators an error aborts the epoch instead of
    silently misaligning the surviving streams."""
    import pytest as _pytest
    from mxnet_tpu.io import (DataIter, DataBatch, NDArrayIter,
                              PrefetchingIter)
    from mxnet_tpu import ndarray as nd

    class Flaky(DataIter):
        def __init__(self):
            super().__init__()
            self.n = 0

        @property
        def provide_data(self):
            return [('data2', (2, 2))]

        @property
        def provide_label(self):
            return []

        def reset(self):
            self.n = 0

        def next(self):
            self.n += 1
            if self.n == 2:
                raise IOError('boom')
            if self.n > 3:
                raise StopIteration
            return DataBatch([nd.ones((2, 2)) * self.n], [], pad=0)

    good = NDArrayIter(np.zeros((6, 2), np.float32), batch_size=2)
    it = PrefetchingIter([good, Flaky()])
    assert it.iter_next()
    with _pytest.raises(IOError):
        it.iter_next()
    assert not it.iter_next()     # epoch aborted
    it.reset()                    # realigns both streams
    assert it.iter_next()


def test_prefetching_iter_released_on_engine_worker():
    """The fetch closure holds the iterator, so the last reference can
    die on the engine's own worker thread when the in-flight fetch
    returns; __del__ there must not wait on the var whose op is the one
    running (it deadlocked the process at exit)."""
    import threading
    from mxnet_tpu.io import DataIter, PrefetchingIter

    entered, gate = threading.Event(), threading.Event()

    class Slow(DataIter):
        provide_data = [('data', (2, 2))]
        provide_label = []

        def next(self):
            entered.set()
            gate.wait(30)
            raise StopIteration

    it = PrefetchingIter(Slow())          # pushes the first fetch at once
    assert entered.wait(30)
    engine = it._engine
    del it                                # the running fetch holds the last ref
    gate.set()
    waiter = threading.Thread(target=engine.wait_for_all, daemon=True)
    waiter.start()
    waiter.join(30)
    assert not waiter.is_alive(), 'the engine worker deadlocked in __del__'
