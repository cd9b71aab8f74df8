"""Profiler (reference ``python/mxnet/profiler.py`` over
``MXSetProfilerConfig/State``, ``src/engine/profiler.cc``).

Thin compatibility shim over :mod:`mxnet_tpu.instrument` — the unified
tracing/metrics layer.  ``record_event``/``Scope`` append to the
per-thread span buffers (with the REAL pid/tid, so multi-threaded traces
no longer collapse into one Perfetto lane) and ``dump_profile`` writes
the full Chrome-trace JSON with ``displayTimeUnit`` and process/thread
metadata.  Explicit calls through this API always record, matching the
legacy contract; flag-gated framework-wide spans are instrument.py's
job.

``profiler_set_state('run')`` additionally starts a JAX/XLA device
trace (Perfetto/TensorBoard, per-HLO timing; a failure to start it
propagates) and turns the instrument span tracer on for the duration.
"""
from __future__ import annotations

import os
import time

import jax

from . import instrument

_state = {'running': False, 'filename': 'profile.json', 'mode': 'symbolic',
          'prev_profile_on': False}


def profiler_set_config(mode='symbolic', filename='profile.json'):
    """(reference profiler.py:10-27)"""
    _state['mode'] = mode
    _state['filename'] = filename


def profiler_set_state(state='stop'):
    """'run' starts a jax profiler trace + the instrument span tracer;
    'stop' ends both (span tracing reverts to its prior setting)."""
    if state == 'run' and not _state['running']:
        jax.profiler.start_trace(
            os.path.splitext(_state['filename'])[0] + '_jax_trace')
        _state['prev_profile_on'] = instrument.profiling_enabled()
        instrument.set_profiling(True)
        _state['running'] = True
    elif state == 'stop' and _state['running']:
        _state['running'] = False
        # restore only what 'run' changed: set_profiling releases the
        # metrics it implied, and leaves an explicit set_metrics(True)
        # made mid-run alone
        instrument.set_profiling(_state['prev_profile_on'])
        jax.profiler.stop_trace()


def record_event(name, begin, end, category='op'):
    """Host-side event for the Chrome-trace dump (engine profiler
    analogue).  ``begin``/``end`` are epoch seconds; recorded with the
    calling thread's real pid/tid."""
    instrument.record_complete(name, begin * 1e6, (end - begin) * 1e6,
                               cat=category)


def dump_profile():
    """Write accumulated events as Chrome-tracing JSON
    (reference MXDumpProfile, profiler.cc).  Drains every thread's span
    buffer, so framework spans recorded under MXTPU_PROFILE land in the
    same file as explicit Scope/record_event calls."""
    instrument.dump_trace(_state['filename'])


class Scope:
    """Context manager timing a region into the host trace."""

    def __init__(self, name, category='python'):
        self.name = name
        self.category = category

    def __enter__(self):
        self._t0 = time.time()
        return self

    def __exit__(self, *exc):
        record_event(self.name, self._t0, time.time(), self.category)
