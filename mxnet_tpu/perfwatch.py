"""Performance-attribution plane — live MFU, step-time breakdown,
device-memory ledger, OOM forensics.

Without it a normal training run exports no performance truth at all —
no way to tell whether a step is compute-bound, feed-bound, or
window-bound, and an HBM OOM dies with a bare stack trace.  TensorFlow
treats profiling/introspection as a
first-class mode of the same runtime (Abadi et al.,
https://arxiv.org/pdf/1605.08695) and the MXNet paper leans on explicit
memory accounting to hit its scaling curve (Chen et al.,
https://arxiv.org/pdf/1512.01274).  This module gives the runtime the
same two senses — where time goes and where bytes live — in four legs,
all riding the PR-1 instrument registry (and therefore the PR-5
telemetry piggyback: a multi-rank job reports per-rank MFU and memory
centrally in ``cluster_status.json``/``.prom``):

1. **Per-executable XLA accounting** — :func:`register_executable`
   captures ``cost_analysis()`` / ``memory_analysis()`` from every AOT
   executable the warm-start subsystem compiles (the fused fit step,
   every BucketingModule bucket, Predictor bucket forwards) plus the
   hot-path fused step itself (``Module._run_fused`` AOT-captures its
   program when this plane is on, so the numbers exist without warm
   start).  FLOPs / bytes accessed / arg+output+temp bytes land as
   ``xla.*`` gauges keyed by program signature, in the
   :func:`executables` table, and in the warmup manifest
   (``compile_cache.record_entry``) so a later process knows the cost
   model before it compiles anything.

2. **Live MFU + step-time breakdown** — :func:`note_step` derives
   ``perf.mfu`` (executable FLOPs x steps/sec over the chip peak —
   ``MXTPU_PEAK_FLOPS`` override, else :func:`device_peaks` per device
   kind) and ``perf.steps_per_sec`` from a rolling window; the
   :func:`phase` context manager attributes wall time to the loop's
   seams (``feed_wait``, ``step_prep``, ``dispatch``, ``window_wait``,
   ``callbacks``, ``metric_drain``, ``epoch_end``, ``device_wait``; on
   the feed thread ``feed_fetch`` and ``feed_stage``; ``compile`` round
   the program's own ``lower().compile()``) as
   ``perf.phase.*`` histograms, under the :func:`fit_step` root of each
   iteration; every one is also a ``mxtpu.``-prefixed annotation in a
   running ``jax.profiler`` trace, on the device planes' clock, and —
   under profiling — a Chrome span.  ``MXTPU_STEP_SAMPLE=N`` fully
   syncs every Nth step (``perf.step_latency`` histogram,
   ``perf.host_syncs`` counter, a ``perf.step`` span with phase
   children) for honest device-step latency without re-introducing
   per-batch syncs — ``metric.host_syncs`` stays untouched, pinned by
   test.

3. **Device-memory ledger** — :func:`ledger_alloc` /
   :func:`ledger_donate` account H2D placements and step outputs by
   allocation site (``ndarray._put``, the executor group's
   ``_place_data``, fused-step outputs) into ``mem.live_bytes`` /
   ``mem.peak_bytes`` gauges with per-site attribution
   (:func:`ledger_top`).  Frees ride ``weakref.finalize`` on the device
   array; a donated buffer is retired at donation time and its
   finalizer then becomes a no-op — the double-count guard.

4. **OOM forensics** — :func:`on_error` at the dispatch sites turns a
   ``RESOURCE_EXHAUSTED`` into a flight-recorder dump (``health.py``
   machinery) carrying the triggering executable's ``memory_analysis``,
   the largest live ledger entries, and the current MFU/phase snapshot:
   an OOM becomes a postmortem instead of a stack trace.

The time before the first step and the host's stalls have spans of the
same seam: :func:`setup` round ``BaseModule.fit``'s bind, parameter and
optimizer initialisation and warm start (``perf.setup.*``), and a
``perf.gc`` span with a ``perf.gc_full`` count for every full
(generation 2) collection of Python's collector, live from import while
the plane is on so that set-up is covered.

Zero overhead with knobs off: every hook is one module-global check
(``tests/test_perfwatch.py`` pins < 2x an inlined ideal floor).
``MXTPU_PERFWATCH=1`` implies the metrics registry the same way
``MXTPU_PROFILE`` does.
"""
from __future__ import annotations

import gc
import hashlib
import threading
import time
import weakref
from collections import deque

from . import config, instrument
from .base import MXNetError

__all__ = [
    'enabled', 'set_enabled', 'refresh', 'activate_fit',
    'extract_cost', 'extract_memory', 'register_executable',
    'executables', 'executable_info', 'clear_executables',
    'PEAKS', 'device_peaks', 'peak_flops', 'mfu', 'roofline_mandatory',
    'note_step', 'phase', 'setup', 'sample_tick', 'sample_sync',
    'ledger_alloc', 'ledger_donate', 'ledger_top', 'ledger_stats',
    'ledger_reset',
    'on_error', 'is_oom', 'forensics_snapshot',
    'note_fuse', 'fuse_cost_delta',
]

# (peak bf16 FLOP/s, peak HBM bytes/s) per device kind; published
# per-chip figures (TPU v5 lite: Google Cloud documentation, "TPU v5e").
# A device kind that is not here is an error, not a default: an MFU
# against another chip's peak is wrong, not approximate.  A CPU run
# that wants an MFU pins the denominator with MXTPU_PEAK_FLOPS.
PEAKS = {
    'TPU v5 lite': (197e12, 819e9),
    'TPU v5': (459e12, 1228e9),
    'TPU v4': (275e12, 1228e9),
    'TPU v6 lite': (918e12, 1640e9),
}

_on = False
_sample_n = 0
_peaks = None              # (flops, bw) once resolved
_lock = threading.Lock()

# the communication-attribution plane (commwatch.py) hooks in here: it
# sets _comm to its own module object at import (perfwatch cannot
# import it at module top — that direction closes the cycle) and
# mirrors its enablement into _comm_on, a plain bool, so the hot-path
# off check is one global read — no function call, no attribute chase
# (the <2x-floor guard in tests/test_perfwatch.py holds).
_comm = None
_comm_on = False

# rolling window of step-completion monotonic timestamps (steps/sec =
# (len-1) / (newest - oldest))
_step_window = deque(maxlen=64)
_sample_count = 0

# (kind, keystr) -> {'kind','key','flops','bytes_accessed',
#                    'arg_bytes','output_bytes','temp_bytes',...}
_executables = {}


# ---------------------------------------------------------------------------
# Enablement
# ---------------------------------------------------------------------------

def refresh():
    """(Re)read the MXTPU_PERFWATCH / MXTPU_STEP_SAMPLE knobs.  Called
    at import and from :func:`activate_fit` so an env var exported
    between fits takes effect; hot-path hooks read the cached module
    globals only."""
    global _sample_n
    _sample_n = max(0, int(config.get('MXTPU_STEP_SAMPLE')))
    set_enabled(config.get('MXTPU_PERFWATCH'))


def set_enabled(on):
    """Runtime toggle (tests; equivalent to exporting MXTPU_PERFWATCH)."""
    global _on
    _on = bool(on)
    if _on and not instrument.metrics_enabled():
        # the plane's output IS the metrics registry — implied on, the
        # same contract as MXTPU_PROFILE
        instrument.set_metrics(True)
    _watch_gc(_on)


def enabled():
    return _on


def comm_enabled():
    """True when the communication-attribution plane (commwatch) is on."""
    return _comm_on


def capture_on():
    """True when ANY plane needs the per-executable capture path in
    ``Module._run_fused`` (AOT lower+compile so cost/memory/collective
    analysis exists) and the per-step :func:`note_step` call — this
    plane or commwatch."""
    return _on or _comm_on


def activate_fit():
    """Called by ``BaseModule.fit`` before the first batch: re-reads the
    knobs and resets the per-fit sampling cadence + steps/sec window so
    every fit's ``perf.*`` series starts clean."""
    global _sample_count
    if _comm is not None:
        _comm.activate_fit()
    refresh()
    if not _on and not comm_enabled():
        return
    _sample_count = 0
    # the comm plane's step-cadence intervals must not span fits either
    _step_window.clear()
    if _on:
        pk, _ = peaks()
        instrument.set_gauge('perf.peak_flops', pk)


# ---------------------------------------------------------------------------
# Leg 1: per-executable XLA accounting
# ---------------------------------------------------------------------------

def extract_cost(compiled):
    """``{'flops': F, 'bytes_accessed': B}`` from a compiled
    executable's ``cost_analysis()`` (zeros when the backend reports
    none)."""
    out = {'flops': 0.0, 'bytes_accessed': 0.0}
    try:
        ca = compiled.cost_analysis()
        out['flops'] = float(ca.get('flops', 0.0) or 0.0)
        out['bytes_accessed'] = float(ca.get('bytes accessed', 0.0) or 0.0)
    except Exception:
        pass
    return out


def extract_memory(compiled):
    """Argument/output/temp/code sizes from ``memory_analysis()``
    (zeros when unavailable) — the memory-waterfall row for one
    executable."""
    out = {'arg_bytes': 0, 'output_bytes': 0, 'temp_bytes': 0,
           'alias_bytes': 0, 'code_bytes': 0}
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            return out
        out['arg_bytes'] = int(getattr(ma, 'argument_size_in_bytes', 0))
        out['output_bytes'] = int(getattr(ma, 'output_size_in_bytes', 0))
        out['temp_bytes'] = int(getattr(ma, 'temp_size_in_bytes', 0))
        out['alias_bytes'] = int(getattr(ma, 'alias_size_in_bytes', 0))
        out['code_bytes'] = int(
            getattr(ma, 'generated_code_size_in_bytes', 0))
    except Exception:
        pass
    return out


_keystr_memo = {}


def _keystr(key):
    """Stable short id of a program signature (sig tuples get hashed —
    a gauge name must be bounded and Prometheus-safe).  Memoized for
    hashable keys: note_step resolves the SAME signature every step."""
    try:
        cached = _keystr_memo.get(key)
    except TypeError:
        cached = None
        key_hashable = False
    else:
        key_hashable = True
        if cached is not None:
            return cached
    s = key if isinstance(key, str) else repr(key)
    if len(s) <= 24 and s.replace('_', '').replace('-', '').isalnum():
        out = s
    else:
        out = hashlib.sha1(s.encode()).hexdigest()[:10]
    if key_hashable:
        if len(_keystr_memo) > 256:
            _keystr_memo.clear()
        _keystr_memo[key] = out
    return out


def register_executable(kind, key, compiled, num_devices=1):
    """Capture compile-time cost/memory accounting for one executable.
    Publishes ``xla.<kind>[<key>].*`` gauges, stores the row in the
    :func:`executables` table, and records it into the warmup manifest
    (when a compile-cache dir is installed) so the next process knows
    the cost model before compiling.  Never raises; returns the info
    row, or None when metrics are off.

    ``num_devices`` is the mesh size the program was partitioned over
    (1 off the sharded path).  XLA's ``cost_analysis`` reports the
    PER-DEVICE partitioned module's flops/bytes, so the row keeps both
    views: ``flops``/``bytes_accessed`` as reported (per-device) and
    ``global_flops`` = per-device × num_devices — what :func:`note_step`
    divides by ``num_devices × peak`` so ``perf.mfu`` stays a
    per-chip-honest fraction in [0, 1] on any mesh."""
    if not instrument.metrics_enabled():
        return None
    try:
        info = {'kind': str(kind), 'key': _keystr(key),
                'num_devices': max(1, int(num_devices))}
        info.update(extract_cost(compiled))
        info.update(extract_memory(compiled))
        info['global_flops'] = info['flops'] * info['num_devices']
        with _lock:
            _executables[(info['kind'], info['key'])] = info
        stem = 'xla.%s[%s]' % (info['kind'], info['key'])
        for field in ('flops', 'bytes_accessed', 'arg_bytes',
                      'output_bytes', 'temp_bytes', 'num_devices',
                      'global_flops'):
            instrument.set_gauge('%s.%s' % (stem, field), info[field])
        instrument.set_gauge('xla.executables', len(_executables))
        if comm_enabled():
            # collective accounting rides the same registration: every
            # AOT site feeds the communication plane for free
            _comm.analyze_executable(info['kind'], info['key'], compiled,
                                     num_devices=info['num_devices'])
        from . import compile_cache
        compile_cache.record_entry({'kind': 'xla_cost',
                                    'program': info['kind'],
                                    'key': info['key'],
                                    'flops': info['flops'],
                                    'num_devices': info['num_devices'],
                                    'global_flops': info['global_flops'],
                                    'bytes_accessed':
                                        info['bytes_accessed'],
                                    'arg_bytes': info['arg_bytes'],
                                    'output_bytes': info['output_bytes'],
                                    'temp_bytes': info['temp_bytes']})
        return info
    except Exception:
        return None


def note_fuse(mode, stats):
    """Report one step-compiler pipeline run (``fuse.PassManager``):
    per-pass ``fuse.pass.<name>.{rewrites,nodes_removed}`` counters and
    a ``fuse.runs`` counter, so the win of each graph rewrite is
    attributable in the same registry as the xla.* cost gauges it
    moves.  One metrics-enabled check when the registry is off."""
    if not instrument.metrics_enabled():
        return
    instrument.inc('fuse.runs')
    for name, st in (stats or {}).items():
        if st.get('rewrites'):
            instrument.inc('fuse.pass.%s.rewrites' % name,
                           int(st['rewrites']))
        if st.get('nodes_removed'):
            instrument.inc('fuse.pass.%s.nodes_removed' % name,
                           int(st['nodes_removed']))


def fuse_cost_delta(before, after, tag='fit_step'):
    """Before/after ``cost_analysis`` delta of a step-compiled
    executable: ``before``/``after`` are :func:`register_executable`
    rows (or any dict with ``flops``/``bytes_accessed``).  Publishes
    ``fuse.cost.<tag>.{flops_delta,bytes_delta}`` gauges (positive =
    the pipeline removed work) and returns the delta dict — the
    attribution surface ``tools/check_fusion.py`` gates."""
    delta = {
        'flops_delta': float(before.get('flops', 0.0) or 0.0)
        - float(after.get('flops', 0.0) or 0.0),
        'bytes_delta': float(before.get('bytes_accessed', 0.0) or 0.0)
        - float(after.get('bytes_accessed', 0.0) or 0.0),
    }
    if instrument.metrics_enabled():
        stem = 'fuse.cost.%s' % _keystr(tag)
        instrument.set_gauge(stem + '.flops_delta',
                             delta['flops_delta'])
        instrument.set_gauge(stem + '.bytes_delta',
                             delta['bytes_delta'])
    return delta


def executables():
    """Snapshot of every registered executable row (report/forensics)."""
    with _lock:
        return [dict(v) for v in _executables.values()]


def executable_info(kind, key):
    with _lock:
        info = _executables.get((str(kind), _keystr(key)))
        return dict(info) if info else None


def clear_executables():
    with _lock:
        _executables.clear()


# ---------------------------------------------------------------------------
# Leg 2a: MFU
# ---------------------------------------------------------------------------

def device_kind():
    """``device_kind`` of the first device of JAX's default backend."""
    import jax
    return jax.devices()[0].device_kind


def _table_row(table, kind):
    """``table``'s row for device ``kind`` by prefix match, or None."""
    for key, row in table.items():
        if str(kind).startswith(key):
            return row
    return None


def lookup_peak(table, kind, override_knob):
    """``table``'s row for device ``kind``.  An unknown kind raises,
    naming the knob that pins the figure — shared by this module's
    :data:`PEAKS` and ``commwatch.ICI_PEAKS``."""
    row = _table_row(table, kind)
    if row is None:
        raise MXNetError(
            'device kind %r is not in the peak table (%s); set %s to pin '
            'the figure' % (kind, ', '.join(sorted(table)), override_knob))
    return row


def device_peaks(kind=None):
    """(peak flops/sec, peak HBM bytes/sec) of a device kind (the
    attached device's when None).  An unknown kind raises."""
    return lookup_peak(PEAKS, device_kind() if kind is None else kind,
                       'MXTPU_PEAK_FLOPS')


def peaks():
    """Resolved (peak_flops, peak_bw).  ``MXTPU_PEAK_FLOPS`` pins the
    flops term, and with it set a device the table lacks (the CPU
    backend in tests) gets a zero bandwidth term instead of raising."""
    global _peaks
    override = float(config.get('MXTPU_PEAK_FLOPS'))
    if _peaks is None:
        kind = device_kind()
        if override > 0 and _table_row(PEAKS, kind) is None:
            return (override, 0.0)
        _peaks = device_peaks(kind)
    return (override, _peaks[1]) if override > 0 else _peaks


def peak_flops():
    return peaks()[0]


def mfu(step_flops, steps_per_sec, peak=None):
    """Model FLOPs utilization: XLA-counted program FLOPs x steps/sec
    over the chip's peak.  0.0 when either term is unknown."""
    if not step_flops or not steps_per_sec:
        return 0.0
    peak = peak if peak else peak_flops()
    if not peak:
        return 0.0
    return float(step_flops) * float(steps_per_sec) / float(peak)


def roofline_mandatory(min_bytes, steps_per_sec, peak_bw=None):
    """Mandatory-traffic roofline fraction: analytic minimum per-step
    HBM bytes x steps/sec over peak bandwidth (<= 1 by construction
    when ``min_bytes`` really is a lower bound; 1 - frac is the
    removable-traffic headroom)."""
    if not min_bytes or not steps_per_sec:
        return 0.0
    peak_bw = peak_bw if peak_bw else peaks()[1]
    if not peak_bw:
        return 0.0
    return float(min_bytes) * float(steps_per_sec) / float(peak_bw)


def note_step(kind, key, nsamples=0):
    """One training step completed dispatch: advance the rolling
    steps/sec window and publish ``perf.mfu`` / ``perf.steps_per_sec``
    / ``perf.step_flops`` — plus, when the communication plane is on,
    feed ``commwatch.on_step`` (comm.step_time cadence histogram,
    comm.bytes_per_step, perf.comm_fraction).  No-op (two flat global
    checks) when both planes are off."""
    if not _on and not _comm_on:
        return
    comm = _comm if _comm_on else None
    now = time.monotonic()
    interval = (now - _step_window[-1]) if _step_window else None
    _step_window.append(now)
    if _on:
        instrument.inc('perf.steps')
        if nsamples:
            instrument.inc('perf.samples', int(nsamples))
    if len(_step_window) >= 2:
        dt = _step_window[-1] - _step_window[0]
        sps = (len(_step_window) - 1) / dt if dt > 0 else 0.0
    else:
        sps = 0.0
    info = None
    if key is not None:
        with _lock:
            info = _executables.get((str(kind), _keystr(key)))
    # per-device vs global accounting under a mesh: cost_analysis
    # counts the partitioned (per-device) module, so the model's step
    # flops are per-device × num_devices and the MFU denominator is
    # num_devices × per-chip peak — the two mesh factors cancel into a
    # per-chip-honest fraction, [0, 1] on any dp×tp layout
    ndev = info.get('num_devices', 1) if info else 1
    flops = (info.get('global_flops') or info['flops'] * ndev) \
        if info else 0.0
    if _on:
        instrument.set_gauge('perf.steps_per_sec', sps)
        instrument.set_gauge('perf.step_flops', flops)
        instrument.set_gauge('perf.num_devices', ndev)
        instrument.set_gauge('perf.mfu',
                             mfu(flops, sps, peak=peak_flops() * ndev))
    if comm is not None:
        comm.on_step(kind, key, interval, flops / ndev if ndev else 0.0)


# ---------------------------------------------------------------------------
# Leg 2b: phase attribution + sampled step sync
# ---------------------------------------------------------------------------

# the shared disabled-path context instrument exports for all planes
_NULL_PHASE = instrument.NULL_CTX


def phase(name):
    """Attribute the wrapped region's wall time to step phase ``name``
    (``perf.phase.<name>`` histogram; a ``mxtpu.perf.phase.<name>``
    annotation in a running ``jax.profiler`` trace; a Chrome span too
    under profiling).  The shared no-op when the plane is off.  Backed
    by ``instrument.hist_span`` — the single time_ns phase clock shared
    with the input-pipeline plane's ``iowatch.stage.*``, so a
    perf.phase child can never stick out of its perf.step parent by
    clock skew (check_trace validates the nesting)."""
    if not _on:
        return _NULL_PHASE
    return instrument.hist_span('perf.phase.' + name, cat='phase')


def setup(name):
    """A span of the time before the first step (``perf.setup.<name>``:
    ``bind``, ``init_params``, ``init_optimizer``, ``warm_start`` in
    ``BaseModule.fit``), on :func:`phase`'s seam.  The shared no-op when
    the plane is off."""
    if not _on:
        return _NULL_PHASE
    return instrument.hist_span('perf.setup.' + name, cat='setup')


# the full collection in progress: collections never overlap, and a
# collection's start and stop callbacks run on the one thread
_gc_span = None


def _on_collect(stage, info):
    """``gc.callbacks`` entry while the plane is on: a full (generation
    2) collection is a ``perf.gc`` span and counts ``perf.gc_full``."""
    global _gc_span
    if info['generation'] != 2:
        return
    if stage == 'start':
        _gc_span = instrument.hist_span('perf.gc', cat='gc')
        _gc_span.__enter__()
    elif _gc_span is not None:
        span, _gc_span = _gc_span, None
        span.__exit__(None, None, None)
        instrument.inc('perf.gc_full')


def _watch_gc(on):
    """Register :func:`_on_collect` while the plane is on, and only
    then."""
    if on and _on_collect not in gc.callbacks:
        # made now, so that a collection's callback never adds to the
        # registry and a set-up without one reads 0
        instrument.histogram('perf.gc')
        instrument.counter('perf.gc_full')
        gc.callbacks.append(_on_collect)
    elif not on and _on_collect in gc.callbacks:
        gc.callbacks.remove(_on_collect)


def fit_step(step_num):
    """The root span of one fit-loop iteration (``perf.fit_step``), from
    asking the iterator for the batch to the return of the last
    batch-end callback, so consecutive roots tile the fit thread's time
    inside an epoch and a root's self time is what no ``phase`` names.
    Same seam as :func:`phase`; in a ``jax.profiler`` trace it is a step
    annotation carrying ``step_num``, the identifier its children share.
    The shared no-op when the plane is off."""
    if not _on:
        return _NULL_PHASE
    return instrument.hist_span('perf.fit_step', cat='fit',
                                step_num=step_num)


def sample_tick():
    """Per-step sampling decision (MXTPU_STEP_SAMPLE=N: the 1st, N+1th,
    ... steps of a fit sample — exactly ceil(nbatch/N) per nbatch-step
    epoch).  False (one flag check) when off."""
    global _sample_count
    if not _on or not _sample_n:
        return False
    _sample_count += 1
    return (_sample_count - 1) % _sample_n == 0


def sample_sync(ticket, t0, ts_us):
    """Full device sync of a SAMPLED step: waits the step's outputs out
    (engine.sync — the honest completion barrier), records the
    dispatch->completion latency as ``perf.step_latency``, counts
    ``perf.host_syncs`` (``metric.host_syncs`` is untouched — this
    plane adds no metric drains), and emits a ``perf.step`` span whose
    phase children carry the breakdown."""
    from .engine import sync
    with phase('device_wait'):
        sync(ticket)
    dt = time.perf_counter() - t0
    instrument.observe_hist('perf.step_latency', dt)
    instrument.inc('perf.host_syncs')
    if instrument.profiling_enabled():
        # span duration on the same clock as ts (and as the phase
        # children) so check_trace's containment check holds exactly
        dur_us = time.time_ns() // 1000 - int(ts_us)
        instrument.record_complete('perf.step', ts_us, max(dur_us, 0),
                                   cat='perf')


# ---------------------------------------------------------------------------
# Leg 3: device-memory ledger
# ---------------------------------------------------------------------------

_ledger_lock = threading.Lock()
_ledger_live = 0
_ledger_peak = 0
_sites = {}                # site -> [live_bytes, allocs]
_by_id = {}                # id(array) -> entry  (removed on free)

# entry: [site, nbytes, freed, array_id]


def _nbytes(arr):
    try:
        return int(arr.nbytes)
    except Exception:
        try:
            n = 1
            for d in arr.shape:
                n *= int(d)
            import numpy as np
            return n * np.dtype(arr.dtype).itemsize
        except Exception:
            return 0


def _publish_ledger_locked():
    instrument.set_gauge('mem.live_bytes', _ledger_live)
    instrument.set_gauge('mem.peak_bytes', _ledger_peak)
    for site, (live, _n) in _sites.items():
        instrument.set_gauge('mem.site[%s].live_bytes' % site, live)


def _retire(entry, counter):
    """Shared free/donate path: idempotent per entry (the double-count
    guard — a donated buffer's later GC finalizer is a no-op)."""
    global _ledger_live
    with _ledger_lock:
        if entry[2]:
            return False
        entry[2] = True
        _ledger_live -= entry[1]
        site = _sites.get(entry[0])
        if site is not None:
            site[0] -= entry[1]
        _by_id.pop(entry[3], None)
        _publish_ledger_locked()
    instrument.inc(counter)
    return True


def _on_gc(entry):
    _retire(entry, 'mem.frees')


def ledger_alloc(site, arr):
    """Account one device allocation/transfer at ``site`` and arm a
    GC finalizer for the free side.  Returns ``arr`` (call sites wrap
    in-line).  One flag check when the plane is off."""
    global _ledger_live, _ledger_peak
    if not _on or arr is None:
        return arr
    n = _nbytes(arr)
    if not n:
        return arr
    entry = [site, n, False, id(arr)]
    try:
        weakref.finalize(arr, _on_gc, entry)
    except TypeError:
        # not weakref-able on this backend: count the alloc, skip
        # free tracking rather than leak an un-freeable live figure
        entry[2] = True
        instrument.inc('mem.allocs')
        return arr
    with _ledger_lock:
        _ledger_live += n
        if _ledger_live > _ledger_peak:
            _ledger_peak = _ledger_live
        s = _sites.get(site)
        if s is None:
            s = _sites[site] = [0, 0]
        s[0] += n
        s[1] += 1
        _by_id[entry[3]] = entry
        _publish_ledger_locked()
    instrument.inc('mem.allocs')
    return arr


def ledger_donate(arr):
    """Mark ``arr``'s buffer as consumed by donation NOW (the compiled
    program invalidated it even though the Python object lingers).  Its
    GC finalizer later finds the entry already retired — the donated
    buffer is never counted twice.  Unknown arrays no-op."""
    if not _on or arr is None:
        return
    entry = _by_id.get(id(arr))
    if entry is not None:
        _retire(entry, 'mem.donations')


def ledger_top(k=8):
    """Top-``k`` allocation sites by live bytes:
    ``[(site, live_bytes, allocs)]``."""
    with _ledger_lock:
        rows = [(site, live, n) for site, (live, n) in _sites.items()]
    rows.sort(key=lambda r: r[1], reverse=True)
    return rows[:k]


def ledger_stats():
    with _ledger_lock:
        return {'live_bytes': _ledger_live, 'peak_bytes': _ledger_peak,
                'sites': {s: {'live_bytes': v[0], 'allocs': v[1]}
                          for s, v in _sites.items()}}


def ledger_reset():
    """Forget all ledger state (tests).  Armed finalizers retire into
    already-freed entries and no-op."""
    global _ledger_live, _ledger_peak
    with _ledger_lock:
        for entry in list(_by_id.values()):
            entry[2] = True
        _by_id.clear()
        _sites.clear()
        _ledger_live = 0
        _ledger_peak = 0


# ---------------------------------------------------------------------------
# Leg 4: OOM forensics
# ---------------------------------------------------------------------------

_OOM_MARKERS = ('resource_exhausted', 'resource exhausted',
                'out of memory', 'oom while')


def is_oom(exc):
    msg = str(exc).lower()
    return any(m in msg for m in _OOM_MARKERS)


def forensics_snapshot(kind=None, key=None, error=None):
    """The OOM postmortem payload: the triggering executable's
    cost/memory analysis, the largest live ledger entries, and the
    current MFU/throughput picture."""
    doc = {'error': str(error)[:2000] if error is not None else None,
           'ledger': {'top': [{'site': s, 'live_bytes': b, 'allocs': n}
                              for s, b, n in ledger_top(8)]},
           'executables': executables()}
    doc['ledger'].update({k: v for k, v in ledger_stats().items()
                          if k != 'sites'})
    info = executable_info(kind, key) if kind is not None and \
        key is not None else None
    doc['executable'] = info or ({'kind': str(kind), 'key': _keystr(key)}
                                 if kind is not None and key is not None
                                 else None)
    try:
        snap = instrument.metrics_snapshot()
        gauges = snap.get('gauges', {})
        doc['perf'] = {g: gauges[g] for g in
                       ('perf.mfu', 'perf.steps_per_sec',
                        'perf.step_flops', 'mem.live_bytes',
                        'mem.peak_bytes') if g in gauges}
        hists = snap.get('histograms') or {}
        doc['phases'] = {name: {'count': h.get('count'),
                                'sum': h.get('sum'),
                                'p50': h.get('p50'), 'p99': h.get('p99')}
                         for name, h in hists.items()
                         if name.startswith('perf.phase.')}
    except Exception:
        pass
    return doc


def on_error(exc, kind=None, key=None):
    """Dispatch-site exception hook: a RESOURCE_EXHAUSTED triggers the
    flight-recorder OOM postmortem (when a recorder is installed —
    ``MXTPU_FLIGHT_RECORDER``) naming the triggering executable and the
    top live buffers.  Any other exception passes through untouched.
    Never raises (it runs inside an except clause already unwinding)."""
    try:
        if not is_oom(exc):
            return None
        instrument.inc('perf.ooms')
        from . import health
        if health.flight_recorder() is None:
            health.install_flight_recorder()
        return health.dump_flight(
            'oom', extra=forensics_snapshot(kind, key, exc))
    except Exception:
        return None


refresh()
