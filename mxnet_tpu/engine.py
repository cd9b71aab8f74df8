"""Engine facade — synchronization and execution-mode control.

The reference's threaded dependency engine
(``src/engine/threaded_engine*.cc``, ``include/mxnet/engine.h:75-229``)
schedules async ops against versioned variables.  On this stack XLA's
per-device in-order async streams provide the same guarantees natively, so
this module only exposes the *control surface* users relied on:

- ``wait_for_var`` / ``wait_for_all`` — ``Engine::WaitForVar/WaitForAll``
  (``engine.h:141-147``);
- ``set_engine_type('Naive'…)`` — the ``MXNET_ENGINE_TYPE`` debug switch
  (``src/engine/engine.cc:13-39``): ``Naive`` disables jit so every op runs
  eagerly and synchronously with a Python backtrace, the same debugging
  story the reference documents for NaiveEngine
  (``threaded_engine.h:336-344``).
"""
from __future__ import annotations

import jax

from . import instrument
from . import iowatch as _iowatch
from . import perfwatch as _perfwatch

_engine_type = 'ThreadedEnginePerDevice'


def set_engine_type(name: str):
    """'NaiveEngine' => synchronous eager execution (jit disabled)."""
    global _engine_type, _native_engine
    _engine_type = name
    jax.config.update('jax_disable_jit', name == 'NaiveEngine')
    if _native_engine is not None and \
            _native_engine._naive != (name == 'NaiveEngine'):
        # rebuild the global native engine in the new mode so host-side
        # pushes honor the switch too (MXNET_ENGINE_TYPE semantics)
        old, _native_engine = _native_engine, None
        old.dispose()


def get_engine_type() -> str:
    return _engine_type


def _arrays(tree):
    """The leaves of ``tree`` with NDArray wrappers unwrapped to their
    jax arrays."""
    return [getattr(leaf, 'handle', leaf)
            for leaf in jax.tree_util.tree_leaves(tree)]


def sync(tree=None):
    """Force completion of every array in ``tree`` (or of all work queued
    on the default device when ``tree`` is None) and return ``tree``.

    This is the engine's ``WaitForVar`` primitive; every timing boundary
    and barrier in the framework goes through it.  It is
    ``jax.block_until_ready``: on the TPU that wait was measured against
    a device-to-host fetch of the same result and is a true barrier
    (``chip_smoke.py``, barrier phase).
    """
    import jax.numpy as _jnp
    with instrument.span('engine.sync', cat='wait'):
        # device streams execute in order: with nothing to wait on, a
        # fresh no-op enqueued now completes only after everything
        # already queued.
        jax.block_until_ready(_arrays(tree) or [_jnp.zeros(())])
        return tree


def wait_for_var(array):
    array.wait_to_read()


def wait_for_all():
    from .ndarray import waitall
    waitall()


def set_bulk_size(size):
    """Engine op bulking knob — XLA fuses automatically; kept as a no-op
    for API parity (``MXEngineSetBulkSize``)."""
    return size


class StepWindow(object):
    """Bounded window of in-flight dispatched training steps.

    XLA dispatch is asynchronous, so without per-batch host syncs the
    fit loop could race arbitrarily far ahead of the device, queueing
    unbounded work (and holding every queued step's input buffers).
    This window is the reference dependency engine's backpressure
    analogue for the sync-free loop: after dispatching step N the loop
    ``admit``\\s a *ticket* (the step's output arrays); once ``depth``
    tickets are in flight the oldest is waited on before the next
    dispatch proceeds.  ``depth=1`` reproduces fully synchronous
    stepping (today's behavior with host-side metrics); ``depth=2``
    (the MXTPU_ASYNC_DEPTH default) overlaps dispatch of step N+1 with
    device execution of step N.

    The current in-flight count is published as the
    ``engine.inflight_depth`` gauge (kept honest across waits/drains)
    and its high-water mark as ``engine.inflight_peak`` so tests can
    assert the overlap actually happened.
    """

    def __init__(self, depth):
        from collections import deque
        self.depth = max(1, int(depth))
        self._inflight = deque()
        self._peak = 0

    def _wait(self, ticket):
        """Completion wait on one ticket."""
        # iowatch.stage.window_wait is the goodput advisor's
        # device-bound signal: a fat window_wait with a thin feed_wait
        # means the DEVICE is the bottleneck (healthy), the inverse
        # means the input pipeline is (input-bound).  The wait itself
        # stays in the productive remainder — the device is training.
        with instrument.span('engine.window_wait', cat='wait'), \
                _perfwatch.phase('window_wait'), \
                _iowatch.stage('window_wait'):
            instrument.inc('engine.window_waits')
            jax.block_until_ready(_arrays(ticket))

    def admit(self, ticket):
        """Register a just-dispatched step; blocks (on the OLDEST step)
        until at most ``depth - 1`` remain in flight, so at most
        ``depth`` dispatched steps ever coexist."""
        if ticket is None:
            return
        self._inflight.append(ticket)
        n = len(self._inflight)
        if n > self._peak:
            self._peak = n
            instrument.set_gauge('engine.inflight_peak', n)
        instrument.set_gauge('engine.inflight_depth', n)
        while len(self._inflight) >= self.depth:
            self._wait(self._inflight.popleft())
            instrument.set_gauge('engine.inflight_depth',
                                 len(self._inflight))

    def drain(self):
        """Wait out every in-flight step (epoch boundaries)."""
        while self._inflight:
            self._wait(self._inflight.popleft())
        instrument.set_gauge('engine.inflight_depth', 0)


# ---------------------------------------------------------------------------
# Native threaded dependency engine (src/engine.cc)
# ---------------------------------------------------------------------------
#
# XLA's in-order async device streams replace the reference engine's
# *device*-side scheduling, but the reference also used the engine for
# host-side async work (IO prefetch stages, checkpoint writes, kvstore CPU
# reductions — all pushed with FnProperty::kNormal/kCPUPrioritized).  The
# native engine provides exactly that: versioned-variable dependency
# scheduling over a C++ worker pool, with WaitForVar/WaitForAll and
# NaiveEngine-style synchronous mode (reference semantics:
# ``src/engine/threaded_engine.h:44-401``).


class Var(object):
    """Handle to a native versioned variable (``Engine::NewVariable``)."""
    __slots__ = ('handle', '_engine')

    def __init__(self, engine, handle):
        self._engine = engine
        self.handle = handle

    @property
    def version(self):
        from ._native import rt_lib
        self._engine._check_alive()
        return rt_lib().MXTPUEngineVarVersion(self._engine._handle,
                                              self.handle)


class NativeEngine(object):
    """ctypes wrapper over the C++ dependency engine.

    ``push(fn, const_vars, mutable_vars)`` mirrors
    ``Engine::PushAsync`` (``include/mxnet/engine.h:104-129``): ``fn``
    runs on a worker thread once every read/write dependency is granted;
    writes to a var are serialized, reads run concurrently.
    """

    def __init__(self, num_workers=None, naive=False):
        from ._native import rt_lib, ENGINE_CALLBACK
        if num_workers is None:
            from . import config
            num_workers = int(config.get('MXNET_CPU_WORKER_NTHREADS'))
        self._lib = rt_lib()
        self._naive = bool(naive)
        self._handle = self._lib.MXTPUEngineCreate(int(num_workers),
                                                   1 if naive else 0)
        self._callbacks = {}
        self._next_id = [1]
        import threading
        self._cb_lock = threading.Lock()

        def _trampoline(ctx):
            with self._cb_lock:
                fn = self._callbacks.pop(int(ctx))
            try:
                fn()
            except Exception:     # never propagate into the C worker
                import traceback
                traceback.print_exc()
        # Must outlive every pending op: stored on self.
        self._trampoline = ENGINE_CALLBACK(_trampoline)

    def new_var(self):
        return Var(self, self._lib.MXTPUEngineNewVar(self._handle))

    def del_var(self, var):
        """Engine::DeleteVariable — frees the var once all ops queued on
        it complete.  The var handle must not be used afterwards."""
        if self._handle and var.handle:
            self._lib.MXTPUEngineDelVar(self._handle, var.handle)
            var.handle = None

    def _check_alive(self):
        if not self._handle:
            raise RuntimeError(
                'native engine has been disposed (set_engine_type '
                'rebuilds the global engine; re-acquire it via '
                'native_engine())')

    def push(self, fn, const_vars=(), mutable_vars=(), priority=0,
             name='op'):
        import ctypes
        self._check_alive()
        handles = [v.handle for v in mutable_vars]
        if len(set(handles)) != len(handles) or \
                set(handles) & {v.handle for v in const_vars}:
            # the reference's CheckDuplicate (threaded_engine.cc:207)
            raise ValueError(
                'const_vars and mutable_vars must be disjoint and '
                'duplicate-free')
        with self._cb_lock:
            cb_id = self._next_id[0]
            self._next_id[0] += 1
            self._callbacks[cb_id] = fn
        nc, nm = len(const_vars), len(mutable_vars)
        carr = (ctypes.c_void_p * max(nc, 1))(
            *[v.handle for v in const_vars])
        marr = (ctypes.c_void_p * max(nm, 1))(
            *[v.handle for v in mutable_vars])
        self._lib.MXTPUEnginePushAsync(
            self._handle, self._trampoline, ctypes.c_void_p(cb_id),
            carr, nc, marr, nm, int(priority), name.encode())

    def wait_for_var(self, var):
        self._check_alive()
        with instrument.span('engine.wait_for_var', cat='wait'):
            self._lib.MXTPUEngineWaitForVar(self._handle, var.handle)

    def wait_for_all(self):
        self._check_alive()
        with instrument.span('engine.wait_for_all', cat='wait'):
            self._lib.MXTPUEngineWaitForAll(self._handle)

    def set_profiling(self, on):
        self._check_alive()
        self._lib.MXTPUEngineSetProfiling(self._handle, 1 if on else 0)

    def dump_profile(self, path):
        self._check_alive()
        if self._lib.MXTPUEngineDumpProfile(self._handle,
                                            str(path).encode()) != 0:
            raise IOError('cannot write profile to %s' % path)

    def dispose(self):
        """Drain pending ops and free the native engine.  Must happen
        before interpreter finalization: worker threads re-enter Python
        through the ctypes trampoline, which is illegal once the
        interpreter starts tearing down."""
        handle = getattr(self, '_handle', None)
        if handle:
            self._handle = None
            self._lib.MXTPUEngineFree(handle)

    def __del__(self):
        import sys
        if sys.is_finalizing():
            return  # leak rather than join threads during teardown
        try:
            self.dispose()
        except Exception:
            pass


_native_engine = None
_atexit_registered = False


def native_engine():
    """The process-global host-side engine (``Engine::Get()``)."""
    global _native_engine, _atexit_registered
    if _native_engine is None:
        _native_engine = NativeEngine(
            naive=(_engine_type == 'NaiveEngine'))
        if not _atexit_registered:
            # engine-type toggles recreate the engine; register the
            # shutdown hook once for the process, not once per engine
            import atexit
            atexit.register(_shutdown_native_engine)
            _atexit_registered = True
    return _native_engine


def _shutdown_native_engine():
    """atexit hook: drain + free the global engine while Python callbacks
    can still run (the reference's ``MXNotifyShutdown``)."""
    global _native_engine
    if _native_engine is not None:
        eng, _native_engine = _native_engine, None
        eng.dispose()
