"""Data iterators (reference ``python/mxnet/io.py:23-590`` and the C++
iterator chain of ``src/io/``).

The reference pipeline is parser → ``BatchLoader`` (batching + last-batch
padding, ``src/io/iter_batchloader.h:36-164``) → ``PrefetcherIter``
(background thread, ``src/io/iter_prefetcher.h:50-151``).  Here the same
stages exist: python iterators batch with identical pad semantics, and
``PrefetchingIter`` runs producers on threads.  Device transfer overlaps
with compute for free because ``jax.device_put`` is async.
"""
from __future__ import annotations

import queue
import sys as _sys
from collections import namedtuple

import numpy as np

from . import instrument
from . import iowatch as _iowatch
from . import perfwatch as _perfwatch
from .base import MXNetError
from . import ndarray as nd
from .ndarray import NDArray, array

DataDesc = namedtuple('DataDesc', ['name', 'shape'])


class DataBatch(object):
    """One mini-batch (reference io.py:60)."""

    def __init__(self, data, label, pad=None, index=None,
                 bucket_key=None, provide_data=None, provide_label=None):
        self.data = data
        self.label = label
        self.pad = pad
        self.index = index
        self.bucket_key = bucket_key
        self.provide_data = provide_data
        self.provide_label = provide_label


class DataIter(object):
    """Base iterator (reference io.py:81)."""

    # each delivered batch bumps io.batches exactly once: 1:1 wrappers
    # (ResizeIter) set this False and let the leaf count, merging
    # wrappers (PrefetchingIter) silence their leaves and count the
    # delivered batch themselves
    _counts_io_batches = True

    def __init__(self):
        self.batch_size = 0

    def __iter__(self):
        return self

    def reset(self):
        pass

    def next(self):
        # time spent producing the next batch on the consuming (fit)
        # thread is input-pipeline time: the goodput ledger charges it
        # to input_stall (no-op off the fit thread / with the plane off)
        with instrument.span('io.next', cat='io'), \
                _iowatch.account('input_stall'):
            if self.iter_next():
                batch = DataBatch(data=self.getdata(),
                                  label=self.getlabel(),
                                  pad=self.getpad(),
                                  index=self.getindex())
                if self._counts_io_batches:
                    instrument.inc('io.batches')
                    _iowatch.note_batch(batch)
                return batch
        raise StopIteration

    def __next__(self):
        return self.next()

    def iter_next(self):
        pass

    def getdata(self):
        pass

    def getlabel(self):
        pass

    def getindex(self):
        return None

    def getpad(self):
        pass

    def provide_signature(self):
        """``{name: (shape, dtype_str)}`` over data+label — what the
        warm-start compiler (compile_cache) needs to pre-lower the
        fused step before the first batch arrives.  The base derives
        shapes from ``provide_data``/``provide_label`` and assumes
        float32; iterators that know their true dtypes override
        (NDArrayIter)."""
        sig = {}
        try:
            for name, shape in (self.provide_data or []):
                sig[name] = (tuple(shape), 'float32')
            for name, shape in (self.provide_label or []):
                sig[name] = (tuple(shape), 'float32')
        except Exception:
            return {}
        return sig


class ResizeIter(DataIter):
    """Resize an iterator to ``size`` batches per epoch (reference io.py:138)."""

    _counts_io_batches = False      # delegates to data_iter

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__()
        self.data_iter = data_iter
        self.size = size
        self.reset_internal = reset_internal
        self.cur = 0
        self.current_batch = None
        self.provide_data = data_iter.provide_data
        self.provide_label = data_iter.provide_label
        self.batch_size = data_iter.batch_size

    def reset(self):
        self.cur = 0
        if self.reset_internal:
            self.data_iter.reset()

    def iter_next(self):
        if self.cur == self.size:
            return False
        try:
            self.current_batch = self.data_iter.next()
        except StopIteration:
            self.data_iter.reset()
            self.current_batch = self.data_iter.next()
        self.cur += 1
        return True

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


def _place_batch(batch, place_data, place_label=None):
    """Stage one DataBatch's arrays onto the device with ``place_data``
    (typically the executor group's ``_place_data`` — batch-sharded on a
    mesh), counting the staged bytes as ``io.h2d_prefetch_bytes``.
    device_put is async, so calling this from a producer thread overlaps
    the transfer with the step running on the device."""
    place_label = place_label or place_data

    def stage(values, place):
        staged = []
        for value in values or []:
            v = value.handle if isinstance(value, NDArray) else \
                np.asarray(value)
            placed = place(v)
            if instrument.metrics_enabled():
                instrument.inc('io.h2d_prefetch_bytes',
                               int(np.prod(placed.shape) *
                                   np.dtype(placed.dtype).itemsize))
            staged.append(NDArray(placed))
        return staged

    # one device_stage sample per BATCH (data + label together), so
    # stage call counts line up one-per-batch with read/decode/batchify
    with _iowatch.stage('device_stage'):
        return DataBatch(stage(batch.data, place_data),
                         stage(batch.label, place_label),
                         pad=batch.pad, index=batch.index,
                         bucket_key=batch.bucket_key,
                         provide_data=batch.provide_data,
                         provide_label=batch.provide_label)


class DeviceFeedIter(DataIter):
    """Double-buffered host→device feed (the PR-3 sync-free loop's H2D
    stage).  Wraps any DataIter: a background worker pulls batch N+1
    from the inner iterator and ``jax.device_put``\\s it with the bound
    executor group's sharding while step N runs on the device — by the
    time the fit loop asks for the next batch its arrays are already
    (asynchronously) in flight to HBM, so the transfer never sits on the
    step's critical path.

    Exactly one fetch is outstanding (the ``iter_prefetcher.h:119-134``
    double-buffer discipline): the next fetch is submitted when the
    previous batch is consumed, which bounds host+device staging memory
    to two batches.  ``close()`` drains the worker and hands the inner
    iterator back in a clean state (resetting it only if a staged batch
    had to be discarded — a normal end-of-fit leaves no fetch pending).

    Because the feed runs one fetch AHEAD of the consumer, io.batches
    counting moves to this wrapper (delivered batches), silencing the
    inner chain like PrefetchingIter — and unlike PrefetchingIter the
    wrap is transparent (Module.fit installs it), so ``close()``
    restores the inner iterators' counting flags.
    """

    def __init__(self, data_iter, place_data, place_label=None):
        super().__init__()
        from concurrent.futures import ThreadPoolExecutor
        self.data_iter = data_iter
        self._place_data = place_data
        self._place_label = place_label or place_data
        self.batch_size = data_iter.batch_size
        self.current_batch = None
        self._silenced = []
        it, seen = data_iter, set()
        while it is not None and id(it) not in seen:
            seen.add(id(it))
            # getattr: duck-typed iterators (synthetic feeds) lack the
            # counting protocol; silencing them is still correct
            self._silenced.append(
                (it, getattr(it, '_counts_io_batches', True)))
            it._counts_io_batches = False
            it = getattr(it, '_inner', None) or \
                getattr(it, 'data_iter', None)
        self._pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix='mxtpu-device-feed')
        self._pending = None
        self._exhausted = False
        self._prime()

    @property
    def provide_data(self):
        return self.data_iter.provide_data

    @property
    def provide_label(self):
        return self.data_iter.provide_label

    def _fetch(self):
        # on the feed thread, one batch ahead of the step that consumes
        # it, so parentless: the inner iterator's work, then the host's
        # share of the copy (conversion, linearising, issuing — the
        # transfer's own time is on the runtime's lines of a trace)
        try:
            with _perfwatch.phase('feed_fetch'):
                batch = self.data_iter.next()
        except StopIteration:
            return None
        with _perfwatch.phase('feed_stage'):
            return _place_batch(batch, self._place_data,
                                self._place_label)

    def _prime(self):
        if self._pending is None:
            self._pending = self._pool.submit(self._fetch)

    def reset(self):
        # LAZY re-prime: the first iter_next() after a reset submits the
        # fetch.  An eager prime here would steal one batch from the
        # just-rewound inner iterator at the FINAL epoch-boundary reset
        # (fit resets after every epoch) — for a non-rewindable source
        # (DataIter.reset defaults to a no-op) that batch would be lost
        # for good.  Cost: one prefetch bubble per epoch boundary, which
        # the boundary's window drain dwarfs anyway.
        self._drain()
        self.data_iter.reset()
        self._exhausted = False

    def _drain(self):
        """Discard the outstanding fetch; True when a REAL staged batch
        (not an exhaustion sentinel/error) was thrown away."""
        if self._pending is None:
            return False
        pending, self._pending = self._pending, None
        try:
            return pending.result() is not None
        except BaseException:
            return False

    def iter_next(self):
        if self._exhausted:             # sticky until reset()
            return False
        if self._pending is None:
            self._prime()               # first request after a reset
        # occupancy: 1 = the staged batch was already waiting (the feed
        # keeps up with the device); 0 = the consumer outran the feed —
        # a sustained 0 with a fat feed_wait histogram is the
        # input-bound signature explain_goodput names.  The enabled()
        # pre-check keeps argument evaluation (a Future poll) off the
        # disabled hot path too, not just the gauge write.
        if _iowatch.enabled():
            _iowatch.set_depth('feed_ready',
                               1.0 if self._pending.done() else 0.0)
        with instrument.span('io.device_feed_wait', cat='io'), \
                _perfwatch.phase('feed_wait'), \
                _iowatch.stage('feed_wait'), \
                _iowatch.account('input_stall'):
            pending, self._pending = self._pending, None
            batch = pending.result()    # re-raises producer errors
        if batch is None:
            self._exhausted = True
            return False
        self._prime()                   # overlap the NEXT fetch
        self.current_batch = batch
        return True

    def next(self):
        # deliver the staged batch itself, not the base-class rebuild:
        # bucket_key / provide_data / provide_label must survive the
        # wrap (BucketingModule.switch_bucket reads them per batch)
        with instrument.span('io.next', cat='io'), \
                _iowatch.account('input_stall'):
            if self.iter_next():
                if self._counts_io_batches:
                    instrument.inc('io.batches')
                    _iowatch.note_batch(self.current_batch)
                return self.current_batch
        raise StopIteration

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad

    def close(self):
        """Drain any outstanding fetch, restore the inner iterators'
        batch-counting flags and stop the worker.  The inner iterator is
        reset ONLY when a staged batch was actually discarded (close
        mid-epoch): after a normal end-of-fit reset() nothing is
        prefetched (lazy re-prime), and a second reset here would
        clobber state the caller owns — e.g. the roll_over cursor."""
        if self._drain():
            try:
                self.data_iter.reset()
            except Exception:
                pass
        for it, old in self._silenced:
            it._counts_io_batches = old
        self._silenced = []
        self._pool.shutdown(wait=False)

    def __del__(self):
        try:
            self._pool.shutdown(wait=False)
        except Exception:
            pass


class PrefetchingIter(DataIter):
    """Prefetch over one or more iterators via the native dependency
    engine (reference io.py:190, C++ ``PrefetcherIter``
    ``iter_prefetcher.h:50-151``).

    Each underlying iterator has one engine variable; fetches are pushed
    as write ops on it, so the engine serializes fetches per iterator
    (the reference got the same guarantee from ``dmlc::ThreadedIter``'s
    single producer thread) while different iterators fetch in parallel
    on the worker pool.  At most one fetch is outstanding per iterator —
    the next is pushed only when the previous batch is consumed, which is
    exactly the double buffering of ``iter_prefetcher.h:119-134``.

    ``device_place`` (a placement function such as the executor group's
    ``_place_data``) additionally stages each fetched batch onto the
    device from the producer thread — the DeviceFeedIter H2D overlap
    fused into the prefetch stage.
    """

    def __init__(self, iters, rename_data=None, rename_label=None,
                 device_place=None):
        super().__init__()
        if not isinstance(iters, list):
            iters = [iters]
        self.n_iter = len(iters)
        assert self.n_iter > 0
        self.iters = iters
        # n_iter inner batches merge into ONE delivered batch, so this
        # wrapper takes over io.batches counting from the iterators it
        # owns — silencing the whole delegation chain (CSVIter/MNISTIter
        # forward next() to an `_inner` leaf, ResizeIter to `data_iter`)
        for it in iters:
            seen = set()
            while it is not None and id(it) not in seen:
                seen.add(id(it))
                it._counts_io_batches = False
                it = getattr(it, '_inner', None) or \
                    getattr(it, 'data_iter', None)
        self.rename_data = rename_data
        self.rename_label = rename_label
        self._device_place = device_place
        self.batch_size = self.provide_data[0][1][0]
        from .engine import native_engine
        self._engine = native_engine()
        self._vars = [self._engine.new_var() for _ in range(self.n_iter)]
        self._results = [queue.Queue() for _ in range(self.n_iter)]
        self.started = True
        self.current_batch = None
        self.next_batch = [None for _ in range(self.n_iter)]
        for i in range(self.n_iter):
            self._push_fetch(i)

    def _ensure_engine(self):
        """Re-acquire the global engine if set_engine_type rebuilt it
        (old vars die with the old engine; recreate them)."""
        if getattr(self._engine, '_handle', None) is None:
            from .engine import native_engine
            self._engine = native_engine()
            self._vars = [self._engine.new_var()
                          for _ in range(self.n_iter)]

    def _push_fetch(self, i):
        def fetch():
            batch = None
            try:
                if self.started:
                    batch = self.iters[i].next()
                    if self._device_place is not None:
                        batch = _place_batch(batch, self._device_place)
            except StopIteration:
                batch = None
            except BaseException as e:   # surface in the consumer thread
                batch = e
            self._results[i].put(batch)
        self._ensure_engine()
        self._engine.push(fetch, mutable_vars=[self._vars[i]],
                          name='prefetch_%d' % i)

    def __del__(self):
        try:
            self.started = False
            if _sys.is_finalizing() or getattr(self._engine, '_handle',
                                               None) is None:
                return
            # Every queued fetch holds this object through its closure,
            # so none is pending when __del__ runs — except the one whose
            # release triggered it, on the engine's own worker thread,
            # where waiting on the var would wait on the op that is
            # running (a deadlock at exit: tools/check_io.py hung in it).
            # del_var frees each var once what is queued on it completed.
            for v in self._vars:
                self._engine.del_var(v)
        except Exception:
            pass

    @property
    def provide_data(self):
        if self.rename_data is None:
            return sum([i.provide_data for i in self.iters], [])
        return sum([[(r[n], s) if isinstance(n, str) else DataDesc(r[n.name], s)
                     for n, s in i.provide_data]
                    for r, i in zip(self.rename_data, self.iters)], [])

    @property
    def provide_label(self):
        if self.rename_label is None:
            return sum([i.provide_label for i in self.iters], [])
        return sum([[(r[n], s) if isinstance(n, str) else DataDesc(r[n.name], s)
                     for n, s in i.provide_label]
                    for r, i in zip(self.rename_label, self.iters)], [])

    def reset(self):
        # drain the outstanding fetch of every iterator, then restart
        for i in range(self.n_iter):
            self._results[i].get()
        self._ensure_engine()
        for i in range(self.n_iter):
            self._engine.wait_for_var(self._vars[i])
        for it in self.iters:
            it.reset()
        for i in range(self.n_iter):
            self._push_fetch(i)

    def iter_next(self):
        # drain every slot first so one failing iterator cannot leave
        # the others' results queued and wedge the protocol
        # enabled() pre-check: the qsize() sweep (one mutex each) must
        # not run on the disabled hot path
        if _iowatch.enabled():
            _iowatch.set_depth('prefetch_depth',
                               min(self._results[i].qsize()
                                   for i in range(self.n_iter)))
        with instrument.span('io.prefetch_wait', cat='io'), \
                _iowatch.stage('prefetch_wait'):
            items = [self._results[i].get() for i in range(self.n_iter)]
        exc = next((x for x in items if isinstance(x, BaseException)),
                   None)
        if exc is not None:
            if self.n_iter == 1:
                # single stream: push a replacement fetch so the caller
                # can retry past a transient error
                self._push_fetch(0)
            else:
                # multiple streams can no longer be realigned (the
                # failing iterator already consumed its batch); abort
                # the epoch — sentinels make the next iter_next() return
                # False and reset() re-syncs every stream from the top
                for i in range(self.n_iter):
                    self._results[i].put(None)
            raise exc
        self.next_batch = items
        if self.next_batch[0] is None:
            for i in self.next_batch:
                assert i is None, 'Number of entry mismatches between iterators'
            # leave a sentinel for reset() to drain
            for i in range(self.n_iter):
                self._results[i].put(None)
            return False
        for batch in self.next_batch:
            assert batch.pad == self.next_batch[0].pad, \
                'Number of entry mismatches between iterators'
        self.current_batch = DataBatch(
            sum([batch.data for batch in self.next_batch], []),
            sum([batch.label for batch in self.next_batch], []),
            self.next_batch[0].pad, self.next_batch[0].index)
        for i in range(self.n_iter):
            self._push_fetch(i)
        return True

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


def _init_data(data, allow_empty, default_name):
    """Normalize input data spec (reference io.py:255)."""
    assert (data is not None) or allow_empty
    if data is None:
        data = []
    if isinstance(data, (np.ndarray, NDArray)):
        data = [data]
    if isinstance(data, list):
        if not allow_empty:
            assert len(data) > 0
        if len(data) == 1:
            data = {default_name: data[0]}
        else:
            data = {('_%d_%s' % (i, default_name)): d
                    for i, d in enumerate(data)}
    if not isinstance(data, dict):
        raise TypeError('Input must be NDArray, numpy.ndarray, a list of '
                        'them or dict with them as values')
    for k, v in data.items():
        if not isinstance(v, NDArray):
            try:
                data[k] = array(v)
            except Exception:
                raise TypeError('Invalid type \'%s\' for %s, should be '
                                'NDArray or numpy.ndarray' % (type(v), k))
    return list(data.items())


class NDArrayIter(DataIter):
    """Iterate over in-memory arrays (reference io.py:295).

    Examples
    --------
    >>> import numpy as np
    >>> it = NDArrayIter(data=np.arange(12.0).reshape(6, 2),
    ...                  label=np.arange(6.0), batch_size=3)
    >>> [b.data[0].shape for b in it]
    [(3, 2), (3, 2)]
    >>> it.reset()
    >>> next(iter(it)).label[0].asnumpy().tolist()
    [0.0, 1.0, 2.0]
    """

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle='pad', data_name='data',
                 label_name='softmax_label'):
        super().__init__()
        self.data = _init_data(data, allow_empty=False,
                               default_name=data_name)
        self.label = _init_data(label, allow_empty=True,
                                default_name=label_name)

        self.idx = np.arange(self.data[0][1].shape[0])
        if shuffle:
            np.random.shuffle(self.idx)
            self.data = [(k, array(v.asnumpy()[self.idx], v.context))
                         for k, v in self.data]
            self.label = [(k, array(v.asnumpy()[self.idx], v.context))
                          for k, v in self.label]

        if last_batch_handle == 'discard':
            new_n = self.data[0][1].shape[0] - \
                self.data[0][1].shape[0] % batch_size
            data_dict = dict(self.data)
            label_dict = dict(self.label)
            for k, _ in self.data:
                data_dict[k] = data_dict[k][:new_n]
            for k, _ in self.label:
                label_dict[k] = label_dict[k][:new_n]
            self.data = [(k, data_dict[k]) for k, _ in self.data]
            self.label = [(k, label_dict[k]) for k, _ in self.label]

        self.data_list = [x[1] for x in self.data] + \
            [x[1] for x in self.label]
        self.num_source = len(self.data_list)
        self.num_data = self.data_list[0].shape[0]
        assert self.num_data >= batch_size, \
            'batch_size need to be smaller than data size.'
        self.cursor = -batch_size
        self.batch_size = batch_size
        self.last_batch_handle = last_batch_handle
        # single-slot cache of the wrapped (padded) final batch, keyed
        # by cursor: the sources are immutable after __init__, so the
        # concatenated view is built once and reused every epoch instead
        # of re-allocating it per wrapped batch (per reset, per source)
        self._pad_cache = {}

    @property
    def provide_data(self):
        return [(k, tuple([self.batch_size] + list(v.shape[1:])))
                for k, v in self.data]

    @property
    def provide_label(self):
        return [(k, tuple([self.batch_size] + list(v.shape[1:])))
                for k, v in self.label]

    def provide_signature(self):
        """Batch signature with the REAL source dtypes (the base class
        assumes float32) — warm-start pre-lowers against these."""
        sig = {}
        for (name, arr), (pname, pshape) in zip(self.data,
                                                self.provide_data):
            sig[pname] = (tuple(pshape), str(np.dtype(arr.dtype)))
        for (name, arr), (pname, pshape) in zip(self.label,
                                                self.provide_label):
            sig[pname] = (tuple(pshape), str(np.dtype(arr.dtype)))
        return sig

    def hard_reset(self):
        self.cursor = -self.batch_size

    def reset(self):
        if self.last_batch_handle == 'roll_over' and \
                self.cursor > self.num_data:
            self.cursor = -self.batch_size + (self.cursor % self.num_data) % \
                self.batch_size
        else:
            self.cursor = -self.batch_size

    def iter_next(self):
        self.cursor += self.batch_size
        return self.cursor < self.num_data

    def _getdata(self, data_source):
        assert self.cursor < self.num_data, 'DataIter needs reset.'
        if self.cursor + self.batch_size <= self.num_data:
            with _iowatch.stage('batchify'):
                return [x[1][self.cursor:self.cursor + self.batch_size]
                        for x in data_source]
        # padding: wrap around (iter_batchloader.h round_batch semantics).
        # The concatenated batch is cached per (source, cursor) — under
        # 'pad' the wrap lands on the same cursor every epoch, so this
        # allocates once per fit instead of once per epoch per source
        tag = 0 if data_source is self.data else 1
        hit = self._pad_cache.get(tag)
        if hit is not None and hit[0] == self.cursor:
            return hit[1]
        with _iowatch.stage('batchify'):
            pad = self.batch_size - self.num_data + self.cursor
            batch = [nd.concatenate([x[1][self.cursor:], x[1][:pad]])
                     for x in data_source]
        self._pad_cache[tag] = (self.cursor, batch)
        return batch

    def getdata(self):
        return self._getdata(self.data)

    def getlabel(self):
        return self._getdata(self.label)

    def getpad(self):
        if self.last_batch_handle == 'pad' and \
                self.cursor + self.batch_size > self.num_data:
            return self.cursor + self.batch_size - self.num_data
        return 0


class MNISTIter(DataIter):
    """MNIST idx-format reader (C++ ``src/io/iter_mnist.cc:241-248``)."""

    def __init__(self, image='train-images-idx3-ubyte',
                 label='train-labels-idx1-ubyte', batch_size=128,
                 shuffle=True, flat=False, silent=False, seed=0,
                 input_shape=None, **kwargs):
        super().__init__()
        import gzip
        import struct as _struct

        def read_idx(path):
            opener = gzip.open if path.endswith('.gz') else open
            with opener(path, 'rb') as f:
                zero, dtype, dims = _struct.unpack('>HBB', f.read(4))
                shape = tuple(_struct.unpack('>I', f.read(4))[0]
                              for _ in range(dims))
                return np.frombuffer(f.read(),
                                     dtype=np.uint8).reshape(shape)

        images = read_idx(image).astype(np.float32) / 255.0
        labels = read_idx(label).astype(np.float32)
        if flat:
            images = images.reshape(images.shape[0], -1)
        else:
            images = images.reshape(images.shape[0], 1,
                                    images.shape[1], images.shape[2])
        if shuffle:
            rng = np.random.RandomState(seed)
            perm = rng.permutation(images.shape[0])
            images, labels = images[perm], labels[perm]
        self._inner = NDArrayIter(images, labels, batch_size,
                                  shuffle=False, last_batch_handle='pad')
        self.batch_size = batch_size

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label

    def reset(self):
        self._inner.reset()

    def next(self):
        return self._inner.next()

    def iter_next(self):
        return self._inner.iter_next()


class CSVIter(DataIter):
    """CSV reader (C++ ``src/io/iter_csv.cc:131-140``)."""

    def __init__(self, data_csv, data_shape, label_csv=None, label_shape=(1,),
                 batch_size=128, round_batch=True, **kwargs):
        super().__init__()
        data = np.loadtxt(data_csv, delimiter=',', dtype=np.float32)
        data = data.reshape((-1,) + tuple(data_shape))
        label = None
        if label_csv is not None:
            label = np.loadtxt(label_csv, delimiter=',', dtype=np.float32)
            label = label.reshape((-1,) + tuple(label_shape))
            if label_shape == (1,):
                label = label.reshape(-1)
        else:
            label = np.zeros((data.shape[0],), dtype=np.float32)
        self._inner = NDArrayIter(
            data, label, batch_size,
            last_batch_handle='pad' if round_batch else 'discard')
        self.batch_size = batch_size

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label

    def reset(self):
        self._inner.reset()

    def next(self):
        return self._inner.next()


def ImageRecordIter(**kwargs):
    """RecordIO image pipeline — native implementation lives in
    mxnet_tpu.io_record (C++ RecordIO + decode); see src/recordio.cc."""
    from .io_record import ImageRecordIter as _Impl
    return _Impl(**kwargs)
