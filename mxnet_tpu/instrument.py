"""Unified tracing + metrics — the framework-wide observability layer.

The reference engine stamps per-op begin/end micros into ``OprExecStat``
records and dumps Chrome-tracing JSON (``src/engine/profiler.h:104-109``,
``profiler.cc``).  This module is that subsystem grown to framework
width, replacing the flat single-lane event buffer of the old
``profiler.py`` shim:

- **Spans** — nested, thread-aware timed regions (:func:`span` context
  manager, :func:`instrumented` decorator).  Each thread appends to its
  own buffer (no lock on the hot path; list.append is atomic under the
  GIL), events carry the real ``pid``/``tid`` so multi-threaded traces
  (IO producers, engine workers, the fit loop) land in separate lanes in
  ``chrome://tracing`` / Perfetto.  :func:`dump_trace` drains every
  buffer into one Chrome-trace JSON with ``process_name``/``thread_name``
  metadata events and ``displayTimeUnit``.
- **Metrics** — a process-wide registry of :class:`Counter` /
  :class:`Gauge` / :class:`Timer` / :class:`Histogram` (bounded
  log-scale buckets with p50/p95/p99 estimates — the serving plane's
  latency SLOs) (executor cache hits vs. retraces,
  samples/sec, transfer bytes, per-phase wall time, device memory via
  ``memory_stats()``).  :func:`metrics_snapshot` returns it as a plain
  dict; :func:`dump_metrics` writes it as JSON.
- **Zero overhead when off** — module-level flags checked before any
  allocation: :func:`span` returns a shared no-op context manager and
  the :func:`inc`/:func:`set_gauge`/:func:`observe` helpers return
  immediately.  ``tests/test_instrument.py`` pins this with a
  microbenchmark so future call sites cannot regress the off path.

Enabled by ``MXTPU_PROFILE`` (spans + metrics) / ``MXTPU_METRICS``
(metrics only) — registered in :mod:`mxnet_tpu.config` — or at runtime
via :func:`set_profiling` / :func:`set_metrics`.
"""
from __future__ import annotations

import bisect
import functools
import json
import os
import re
import sys
import threading
import time
import weakref

from . import config

__all__ = [
    'span', 'instrumented', 'dump_trace', 'trace_events', 'clear_trace',
    'record_complete',
    'recent_events', 'dropped_totals',
    'counter', 'gauge', 'timer', 'histogram', 'counter_value',
    'drop_metric', 'drop_labeled_metrics',
    'hist_delta', 'hist_merge', 'HistogramWindow',
    'inc', 'set_gauge', 'observe', 'observe_hist', 'timed', 'hist_span',
    'add_device_source', 'take_device_sources',
    'decision', 'recent_decisions', 'on_decision', 'remove_decision_sink',
    'count_traces', 'count_trace', 'trace_redirect',
    'metrics_snapshot', 'dump_metrics', 'reset_metrics',
    'render_prometheus', 'split_labeled_name',
    'device_memory_stats',
    'set_profiling', 'set_metrics', 'profiling_enabled', 'metrics_enabled',
    'when_metrics_on',
]

# Cap per-thread buffered events so an always-on trace cannot grow
# without bound; overflow is counted, not silently ignored.
MAX_EVENTS_PER_THREAD = 1 << 20

_profile_on = False
_metrics_on = False
# metrics are on only because set_profiling(True) implied them — so
# set_profiling(False) can release them again without clobbering an
# explicit MXTPU_METRICS / set_metrics(True)
_metrics_implied = False


# ---------------------------------------------------------------------------
# Enable flags
# ---------------------------------------------------------------------------

def _refresh_from_env():
    """(Re)read MXTPU_PROFILE / MXTPU_METRICS.  Profiling implies
    metrics: a trace without its counters answers only half of 'where
    did the milliseconds go'."""
    global _profile_on, _metrics_on, _metrics_implied
    _profile_on = bool(config.get('MXTPU_PROFILE'))
    explicit = bool(config.get('MXTPU_METRICS'))
    _metrics_on = _profile_on or explicit
    _metrics_implied = _profile_on and not explicit


def set_profiling(on):
    """Toggle span tracing.  Turning it on implies metrics; turning it
    off releases metrics again unless they were enabled explicitly."""
    global _profile_on, _metrics_on, _metrics_implied
    _profile_on = bool(on)
    if _profile_on:
        if not _metrics_on:
            _metrics_implied = True
        _metrics_on = True
        _metrics_turned_on()
    elif _metrics_implied:
        _metrics_on = False
        _metrics_implied = False


def set_metrics(on):
    global _metrics_on, _metrics_implied
    _metrics_on = bool(on)
    _metrics_implied = False
    if _metrics_on:
        _metrics_turned_on()


# what waits for the registry's first turn on (compile_cache's
# jax.monitoring listener): a process whose metrics stay off runs none
_when_on = []


def when_metrics_on(fn):
    """Call ``fn()`` once: now if the registry is on, else the first
    time :func:`set_metrics` or :func:`set_profiling` turns it on."""
    if _metrics_on:
        fn()
    else:
        _when_on.append(fn)


def _metrics_turned_on():
    while _when_on:
        _when_on.pop(0)()


def profiling_enabled():
    return _profile_on


def metrics_enabled():
    return _metrics_on


# ---------------------------------------------------------------------------
# Span buffers (one per thread, registered once)
# ---------------------------------------------------------------------------

class _ThreadBuffer(object):
    __slots__ = ('events', 'pid', 'tid', 'thread_name', 'dropped',
                 'dropped_reported', 'thread')

    def __init__(self):
        self.events = []
        self.pid = os.getpid()
        self.tid = threading.get_ident()
        self.thread_name = threading.current_thread().name
        # monotonic, written only by the owning thread; the drainer
        # tracks how many it has reported instead of resetting, so
        # neither side ever needs a lock for it
        self.dropped = 0
        self.dropped_reported = 0
        # weakref: liveness probe for drain-time pruning without keeping
        # retired thread objects alive
        self.thread = weakref.ref(threading.current_thread())


_buffers = []                     # every live/retired thread buffer
# reentrant, as the registry's lock is: a full collection's callback
# (perfwatch's ``perf.gc`` span) may record on a thread that holds it
_buffers_lock = threading.RLock()
# serializes drainers against each other (the events list itself needs
# no lock: append vs slice-copy/slice-delete are each GIL-atomic, and
# the dropped counter is single-writer monotonic)
_drain_lock = threading.Lock()
_tls = threading.local()


def _buffer():
    buf = getattr(_tls, 'buf', None)
    if buf is None:
        buf = _ThreadBuffer()
        with _buffers_lock:
            _buffers.append(buf)
        _tls.buf = buf
    return buf


def _append_event(event):
    """Stamp the calling thread's pid/tid onto ``event`` and buffer it
    (single home of the MAX_EVENTS_PER_THREAD overflow policy)."""
    buf = _buffer()
    event['pid'] = buf.pid
    event['tid'] = buf.tid
    if len(buf.events) >= MAX_EVENTS_PER_THREAD:
        buf.dropped += 1          # single writer: only the owning thread
        return
    buf.events.append(event)


class _NullSpan(object):
    """The disabled path: one shared instance, no allocation per use."""
    __slots__ = ()

    def __enter__(self):
        return self

    def cancel(self):
        pass

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()
# the shared disabled-path context for EVERY observability plane
# (perfwatch.phase, iowatch.stage/account, span/timed here): one
# instance, one class to keep in sync with the zero-overhead-off
# contract
NULL_CTX = _NULL_SPAN


class _Span(object):
    __slots__ = ('name', 'cat', 'args', '_t0')

    def __init__(self, name, cat, args):
        self.name = name
        self.cat = cat
        self.args = args

    def __enter__(self):
        self._t0 = time.time_ns()
        return self

    def __exit__(self, *exc):
        dur = time.time_ns() - self._t0
        event = {'name': self.name, 'cat': self.cat, 'ph': 'X',
                 'ts': self._t0 // 1000, 'dur': max(dur, 0) // 1000}
        if self.args:
            event['args'] = self.args
        _append_event(event)
        return False


def span(name, cat='host', args=None):
    """Timed region as a Chrome-trace complete ('X') event.  Nesting is
    implicit: inner spans on the same thread have shorter durations and
    Perfetto stacks them.  When profiling is off this returns a shared
    no-op context manager — callers on hot paths should not build
    ``args`` dicts inline (compute them behind :func:`profiling_enabled`
    or skip them)."""
    if not _profile_on:
        return _NULL_SPAN
    return _Span(name, cat, args)


def instrumented(name=None, cat='host'):
    """Decorator form of :func:`span` (the flag is checked per call, so
    decorated functions stay free when profiling is off)."""
    def deco(fn):
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*a, **kw):
            if not _profile_on:
                return fn(*a, **kw)
            with _Span(label, cat, None):
                return fn(*a, **kw)
        return wrapper
    return deco


def record_complete(name, ts_us, dur_us, cat='op', args=None):
    """Append a complete event with explicit timestamps, UNCONDITIONALLY
    (no enabled-flag check).  This is the primitive under the legacy
    ``profiler.record_event``/``Scope`` API, whose contract is that an
    explicit call always records."""
    event = {'name': name, 'cat': cat, 'ph': 'X', 'ts': ts_us,
             'dur': max(dur_us, 0)}
    if args:
        event['args'] = args
    _append_event(event)


def _drain_events():
    with _buffers_lock:
        bufs = list(_buffers)
    events = []
    dropped = 0
    # _drain_lock serializes drainers against each other (dump_trace vs
    # the profiler shim's dump_profile vs clear_trace): two concurrent
    # take-prefix/delete-prefix sequences would hand the same events to
    # both and delete events neither copied.  Appenders stay lock-free.
    with _drain_lock:
        for buf in bufs:
            # the owning thread may be appending concurrently: take a
            # length snapshot and delete exactly that prefix (slice copy
            # and slice delete are each one GIL-atomic op), so a race
            # loses nothing — a mid-drain append simply stays buffered
            n = len(buf.events)
            taken = buf.events[:n]
            del buf.events[:n]
            events.extend(taken)
            # dropped is monotonic (owning thread only); report the
            # delta since the last drain — no reset, so a concurrent
            # increment is never lost, merely reported next time
            d = buf.dropped
            dropped += d - buf.dropped_reported
            buf.dropped_reported = d
    # prune buffers of finished threads so per-epoch IO producer threads
    # don't grow _buffers and the metadata section without bound.  Only
    # dead AND empty: a thread that appended its final event after the
    # length snapshot above and then exited still has events to dump.
    def _dead(b):
        t = b.thread()
        return (t is None or not t.is_alive()) and not b.events
    dead = [b for b in bufs if _dead(b)]
    if dead:
        with _buffers_lock:
            for b in dead:
                if b in _buffers:
                    _buffers.remove(b)
    events.sort(key=lambda e: e.get('ts', 0))
    return events, bufs, dropped


def trace_events():
    """Snapshot of currently buffered events (not drained, no metadata)."""
    with _buffers_lock:
        bufs = list(_buffers)
    events = []
    for buf in bufs:
        events.extend(list(buf.events))
    events.sort(key=lambda e: e.get('ts', 0))
    return events


def recent_events(limit=256):
    """The newest ``limit`` buffered span events across all threads,
    sorted by timestamp — WITHOUT draining (``dump_trace`` still sees
    everything).  This is the flight recorder's read path: cheap (tail
    slices per buffer, each one GIL-atomic against the appending owner)
    and safe from any thread, including signal handlers."""
    with _buffers_lock:
        bufs = list(_buffers)
    events = []
    for buf in bufs:
        evs = buf.events
        n = len(evs)
        events.extend(evs[n - limit if n > limit else 0:n])
    events.sort(key=lambda e: e.get('ts', 0))
    return events[-limit:] if len(events) > limit else events


def dropped_totals():
    """Total events ever dropped by the bounded per-thread buffers —
    cumulative and non-destructive (drain-delta accounting in
    ``dump_trace`` is untouched), so overflow is visible from the
    flight recorder too, not only from a full trace dump."""
    with _buffers_lock:
        return sum(b.dropped for b in _buffers)


def clear_trace():
    _drain_events()


def dump_trace(path):
    """Drain every thread buffer into ``path`` as Chrome-trace JSON.

    Metadata (``process_name`` / ``thread_name``, ph='M') is appended
    AFTER the data events — valid anywhere in the array per the trace
    format, and existing consumers index the first data event directly.
    Returns the number of data events written.
    """
    events, bufs, dropped = _drain_events()
    meta = []
    seen_pids = set()
    seen_threads = set()
    for buf in bufs:
        if buf.pid not in seen_pids:
            seen_pids.add(buf.pid)
            meta.append({'name': 'process_name', 'ph': 'M', 'pid': buf.pid,
                         'args': {'name': 'mxnet_tpu'}})
        # dedup on (pid, tid, NAME), not (pid, tid): the OS reuses
        # thread ids, so a retired thread's buffer and a live thread
        # that inherited its tid can coexist in one dump — emit both
        # names rather than letting either mask the other (duplicate
        # thread_name records per tid are legal in the trace format)
        key = (buf.pid, buf.tid, buf.thread_name)
        if key not in seen_threads:
            seen_threads.add(key)
            meta.append({'name': 'thread_name', 'ph': 'M', 'pid': buf.pid,
                         'tid': buf.tid,
                         'args': {'name': buf.thread_name}})
    doc = {'traceEvents': events + meta, 'displayTimeUnit': 'ms'}
    if dropped:
        doc['mxtpuDroppedEvents'] = dropped
    with open(path, 'w') as f:
        json.dump(doc, f)
    return len(events)


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

class Counter(object):
    """Monotonic accumulator (ops, bytes, cache hits).  Incremented
    from multiple threads (IO producers + the fit loop), so the
    read-modify-write takes the registry lock — += alone can lose
    updates when the GIL preempts between load and store."""
    __slots__ = ('name', 'value')

    def __init__(self, name):
        self.name = name
        self.value = 0

    def inc(self, n=1):
        with _metrics_lock:
            self.value += n


class Gauge(object):
    """Last-write-wins instantaneous value (samples/sec, memory bytes)."""
    __slots__ = ('name', 'value')

    def __init__(self, name):
        self.name = name
        self.value = 0.0

    def set(self, value):
        self.value = value


class Timer(object):
    """Accumulated wall time + call count.  Time a region with
    :func:`timed` — the registry Timer is shared per name, so it must
    not hold a start timestamp itself (nested/concurrent use would
    clobber it)."""
    __slots__ = ('name', 'total', 'count')

    def __init__(self, name):
        self.name = name
        self.total = 0.0
        self.count = 0

    def observe(self, seconds):
        with _metrics_lock:
            self.total += seconds
            self.count += 1

    @property
    def avg(self):
        return self.total / self.count if self.count else 0.0


def _quantile_from_counts(counts, total, q):
    """The ONE bucket-walk quantile estimator (cumulative walk +
    linear interpolation inside the landing bucket) behind
    ``Histogram.quantile`` AND the windowed/merged snapshot views
    (:func:`hist_delta` / :func:`hist_merge`) — shared so the p99 the
    autoscaler acts on can never diverge from the p99 the lifetime
    snapshots report.  ``counts`` is a full per-bucket list indexed
    like :data:`HIST_EDGES` (+1 overflow).  Returns 0.0 when empty."""
    if not total:
        return 0.0
    target = q * total
    cum = 0
    for i, c in enumerate(counts):
        if not c:
            continue
        if cum + c >= target:
            lo = HIST_EDGES[i - 1] if i > 0 else 0.0
            hi = HIST_EDGES[i] if i < len(HIST_EDGES) else HIST_EDGES[-1]
            return lo + (hi - lo) * (target - cum) / c
        cum += c
    return HIST_EDGES[-1]


# Fixed log-scale bucket upper bounds shared by every Histogram:
# quarter-decades from 1us to 100s (observations are seconds).  A fixed
# layout keeps memory bounded (34 ints per histogram, forever), makes
# concurrent histograms mergeable bucket-for-bucket, and matches the
# Prometheus histogram model (cumulative le= buckets + +Inf).
HIST_EDGES = tuple(10.0 ** (e / 4.0) for e in range(-24, 9))


class Histogram(object):
    """Bounded-memory latency histogram: fixed log-scale buckets
    (:data:`HIST_EDGES`), a running sum and count, and log-linear
    quantile estimates (p50/p95/p99 for the serving SLO counters).
    Observed from multiple threads, so the read-modify-write takes the
    registry lock like :class:`Counter`.

    ``observe(value, exemplar=...)`` additionally remembers the LAST
    exemplar id (a serving request id) per bucket — bounded at one per
    bucket forever — so a bad ``le=`` bucket in a scrape links to a
    concrete request postmortem (the request-attribution plane,
    docs/serving.md).  Histograms observed without exemplars carry
    none and snapshot/render exactly as before."""
    __slots__ = ('name', 'counts', 'sum', 'count', 'exemplars')

    def __init__(self, name):
        self.name = name
        self.counts = [0] * (len(HIST_EDGES) + 1)   # +1: overflow
        self.sum = 0.0
        self.count = 0
        self.exemplars = None         # bucket idx -> (id, value), lazy

    def observe(self, value, exemplar=None):
        value = float(value)
        with _metrics_lock:
            idx = bisect.bisect_left(HIST_EDGES, value)
            self.counts[idx] += 1
            self.sum += value
            self.count += 1
            if exemplar is not None:
                if self.exemplars is None:
                    self.exemplars = {}
                self.exemplars[idx] = (str(exemplar), value)

    def quantile(self, q):
        """Estimate the ``q`` quantile (0 < q <= 1) by walking the
        cumulative bucket counts and interpolating linearly inside the
        landing bucket.  Returns 0.0 when empty."""
        with _metrics_lock:
            counts = list(self.counts)
            total = self.count
        return _quantile_from_counts(counts, total, q)

    def snapshot(self):
        """JSON form: count/sum/quantiles plus the CUMULATIVE nonzero
        buckets (``[le, cum_count]`` pairs, Prometheus semantics).
        When any observation carried an exemplar, an ``exemplars`` key
        rides along (``[le, id, value]`` triples); exemplar-free
        histograms snapshot byte-identically to before."""
        with _metrics_lock:
            counts = list(self.counts)
            total, s = self.count, self.sum
            ex = dict(self.exemplars) if self.exemplars else None
        buckets = []
        cum = 0
        for i, c in enumerate(counts):
            cum += c
            if c:
                le = HIST_EDGES[i] if i < len(HIST_EDGES) else '+Inf'
                buckets.append([le, cum])
        snap = {'count': total, 'sum': s,
                'p50': self.quantile(0.50), 'p95': self.quantile(0.95),
                'p99': self.quantile(0.99), 'buckets': buckets}
        if ex:
            snap['exemplars'] = [
                [HIST_EDGES[i] if i < len(HIST_EDGES) else '+Inf',
                 rid, val]
                for i, (rid, val) in sorted(ex.items())]
        return snap


# edge value -> index into HIST_EDGES.  Snapshot bucket edges are the
# HIST_EDGES floats themselves (JSON round-trips a Python float
# exactly), so windowed math can map any serialized snapshot back onto
# the shared bucket layout without guessing.
_EDGE_INDEX = {e: i for i, e in enumerate(HIST_EDGES)}


def _bucket_counts(snapshot):
    """Per-bucket (non-cumulative) counts of a Histogram snapshot as a
    full-length list indexed like :data:`HIST_EDGES` (+1 overflow).
    Tolerates unknown edges by folding them into the covering bucket."""
    counts = [0] * (len(HIST_EDGES) + 1)
    prev = 0
    for le, cum in (snapshot or {}).get('buckets') or []:
        c = int(cum) - prev
        prev = int(cum)
        if c <= 0:
            continue
        if isinstance(le, str):              # '+Inf'
            idx = len(HIST_EDGES)
        else:
            idx = _EDGE_INDEX.get(float(le))
            if idx is None:
                idx = min(bisect.bisect_left(HIST_EDGES, float(le)),
                          len(HIST_EDGES))
        counts[idx] += c
    return counts


def _counts_to_snapshot(counts, total, s):
    """Assemble a snapshot-shaped dict (count/sum/p50/p95/p99/buckets)
    from a full per-bucket count list — the shared renderer behind
    :func:`hist_delta` and :func:`hist_merge`."""
    def quantile(q):
        return _quantile_from_counts(counts, total, q)

    buckets = []
    cum = 0
    for i, c in enumerate(counts):
        cum += c
        if c:
            le = HIST_EDGES[i] if i < len(HIST_EDGES) else '+Inf'
            buckets.append([le, cum])
    return {'count': total, 'sum': s, 'p50': quantile(0.50),
            'p95': quantile(0.95), 'p99': quantile(0.99),
            'buckets': buckets}


def hist_delta(cur, prev=None):
    """WINDOWED Histogram view: the delta between two CUMULATIVE
    snapshots (``prev`` taken earlier than ``cur``), as a snapshot-
    shaped dict whose count/sum/quantiles describe only the
    observations that landed BETWEEN the two — what a closed-loop
    controller (the serving autoscaler) must read instead of lifetime
    aggregates, where an old good hour hides the bad minute.  ``prev``
    None (or empty) returns ``cur`` re-derived through the same path.
    A ``cur`` older than ``prev`` (registry reset between snapshots)
    clamps to empty rather than going negative."""
    cur = cur or {}
    cc = _bucket_counts(cur)
    total = int(cur.get('count', 0))
    s = float(cur.get('sum', 0.0))
    if prev:
        pc = _bucket_counts(prev)
        cc = [max(0, a - b) for a, b in zip(cc, pc)]
        total = max(0, total - int(prev.get('count', 0)))
        s = max(0.0, s - float(prev.get('sum', 0.0)))
    return _counts_to_snapshot(cc, total, s)


def hist_merge(snapshots):
    """Merge several Histogram snapshots (same fixed bucket layout —
    every :class:`Histogram` shares :data:`HIST_EDGES`) into one:
    counts add bucket-for-bucket, quantiles re-estimated on the merged
    distribution.  This is the label-merge behind the model-level
    serving view: per-replica/per-lane histograms stay attributable
    while the autoscaler reads their union."""
    counts = [0] * (len(HIST_EDGES) + 1)
    total, s = 0, 0.0
    for snap in snapshots:
        if not snap:
            continue
        for i, c in enumerate(_bucket_counts(snap)):
            counts[i] += c
        total += int(snap.get('count', 0))
        s += float(snap.get('sum', 0.0))
    return _counts_to_snapshot(counts, total, s)


class HistogramWindow(object):
    """Rolling window over registry histograms: each :meth:`delta` call
    returns the windowed view (:func:`hist_delta`) since the LAST call
    for that name and advances the window.  One instance per consumer —
    the serving autoscaler and ``tools/serve_bench.py`` each keep their
    own, so neither steals the other's window."""

    def __init__(self):
        self._prev = {}

    def delta(self, name):
        """Windowed snapshot of histogram ``name`` since the previous
        ``delta(name)`` (first call: since process start).  Returns an
        empty windowed snapshot when the histogram does not exist —
        and FORGETS the window base for it: the series was retired
        (scale_down / unload dropped its labels), so when the slot is
        later reused and the series recreated, its fresh counts must
        not be clamped against the dead series' larger totals (the
        resurrection bug: a reused replica slot would read as silent
        for a whole window)."""
        m = _metrics.get(name)
        if not isinstance(m, Histogram):
            self._prev.pop(name, None)
            return hist_delta({}, None)
        cur = m.snapshot()
        prev = self._prev.get(name)
        self._prev[name] = cur
        return hist_delta(cur, prev)

    def merged_delta(self, names):
        """:func:`hist_merge` of the windowed deltas of ``names`` —
        the one-call model-level read over per-replica/per-lane
        histogram series."""
        return hist_merge([self.delta(n) for n in names])

    def peek_names(self, prefix):
        """Registry histogram names starting with ``prefix`` (labeled
        series included) — how a consumer discovers the per-replica
        series to merge without hardcoding label sets."""
        with _metrics_lock:
            return sorted(n for n, m in _metrics.items()
                          if isinstance(m, Histogram)
                          and n.startswith(prefix))

    def merged_delta_labeled(self, prefix, **labels):
        """:func:`hist_merge` of the windowed deltas of every labeled
        series under ``prefix`` whose parsed labels match ``labels`` —
        the ONE home of the "model-level windowed read over
        per-replica/per-lane series" convention (the serving
        autoscaler's control input and ``serve_bench``'s
        ``server_p99_ms`` cross-check)."""
        live = set(self.peek_names(prefix))
        # prune window bases of RETIRED series under this prefix (a
        # dropped replica's labels): the merged read never touches
        # them again, and a stale base would clamp a later recreation
        # of the same name (slot reuse) to empty for one window
        for n in [k for k in self._prev
                  if k.startswith(prefix) and k not in live]:
            del self._prev[n]
        names = []
        for n in sorted(live):
            _, nl = split_labeled_name(n)
            if nl and all(nl.get(k) == str(v)
                          for k, v in labels.items()):
                names.append(n)
        return hist_merge([self.delta(n) for n in names])


_jax_profiler = None


def _profiler_annotation(name, step_num):
    """The profiler-side sink of one :class:`_HistSpan`: a
    ``jax.profiler`` annotation named ``mxtpu.`` + ``name``.  While a
    ``jax.profiler`` session runs it lands on the ``/host:CPU`` plane of
    the ``.xplane.pb``, on the clock of the device planes and on the
    line of the calling thread; without a session a ``TraceMe`` is a
    flag test.  The one prefix lets a reader pick the program's spans.
    jax is imported on first use, so a process with every plane off
    never pays for it here."""
    global _jax_profiler
    if _jax_profiler is None:
        import jax.profiler as _jax_profiler
    if step_num is None:
        return _jax_profiler.TraceAnnotation('mxtpu.' + name)
    return _jax_profiler.StepTraceAnnotation('mxtpu.' + name,
                                             step_num=step_num)


class _HistSpan(object):
    """One timed region with three sinks: a latency histogram, a
    ``jax.profiler`` annotation (:func:`_profiler_annotation`) and —
    under profiling — a Chrome trace span.  Histogram and Chrome span
    come off a single ``time_ns`` read per edge: this is the shared
    phase clock of the attribution planes (``perf.fit_step``,
    ``perf.phase.*``, ``iowatch.stage.*``), so a phase event can never
    stick out of its enclosing step span by clock skew
    (``tools/check_trace.py`` validates the nesting).  The annotation
    is entered first and left last, so on the profiler's clock too a
    child lies inside its parent."""
    __slots__ = ('name', 'cat', '_t0', '_annotation')

    def __init__(self, name, cat, step_num=None):
        self.name = name
        self.cat = cat
        self._annotation = _profiler_annotation(name, step_num)

    def __enter__(self):
        self._annotation.__enter__()
        self._t0 = time.time_ns()
        return self

    def cancel(self):
        """The region ended without the work it was opened for (the fit
        loop asked an exhausted iterator for a batch): no histogram
        sample and no Chrome span, so counts stay counts of work.  An
        annotation once entered cannot be withdrawn from a profiler's
        trace; it stays there marked ``cancelled=1``."""
        self._t0 = None
        self._annotation.set_metadata(cancelled=1)

    def __exit__(self, *exc):
        if self._t0 is not None:
            dt = time.time_ns() - self._t0
            observe_hist(self.name, dt / 1e9)
            if _profile_on:
                record_complete(self.name, self._t0 // 1000,
                                max(dt, 0) // 1000, cat=self.cat)
        self._annotation.__exit__(*exc)
        return False


def hist_span(name, cat='phase', step_num=None):
    """Histogram+span region factory (see :class:`_HistSpan`).  NOT
    flag-gated itself — callers (perfwatch.phase, iowatch.stage) check
    their own plane's enable flag and return a shared no-op when off.
    ``step_num`` makes the profiler annotation a step annotation that
    carries the number (the root of a fit iteration)."""
    return _HistSpan(name, cat, step_num)


class _TimedCtx(object):
    """One timed region: owns its start timestamp, reports into the
    shared Timer on exit."""
    __slots__ = ('_timer', '_t0')

    def __init__(self, timer):
        self._timer = timer

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._timer.observe(time.perf_counter() - self._t0)
        return False


_metrics = {}
# reentrant: Python's collector runs its callbacks on whichever thread
# allocated, and perfwatch's ``perf.gc`` callback observes into this
# registry, possibly on a thread that already holds the lock
_metrics_lock = threading.RLock()


def _get_metric(name, cls):
    m = _metrics.get(name)
    if m is None:
        with _metrics_lock:
            m = _metrics.get(name)
            if m is None:
                m = _metrics[name] = cls(name)
    if not isinstance(m, cls):
        raise TypeError('metric %r is a %s, not a %s'
                        % (name, type(m).__name__, cls.__name__))
    return m


def counter(name):
    return _get_metric(name, Counter)


def counter_value(name, default=0):
    """Read a counter WITHOUT creating it (registry consumers polling
    names that may not exist yet — the serving autoscaler's windowed
    shed read)."""
    m = _metrics.get(name)
    return m.value if isinstance(m, Counter) else default


def drop_metric(name):
    """Remove one metric from the registry (True when it existed).
    For labeled per-entity series whose entity is GONE — an unloaded
    model's ``serving.replicas|model=...`` gauge must stop being
    scraped, not report its last live value forever."""
    with _metrics_lock:
        return _metrics.pop(name, None) is not None


def drop_labeled_metrics(**labels):
    """Remove EVERY labeled series whose parsed labels match all the
    given ``key=value`` pairs; returns the number dropped.  The bulk
    form of :func:`drop_metric`: unloading a served model must retire
    its whole per-model/per-replica/per-lane series family, or a
    long-lived server churning model names grows the registry (and the
    exposition) without bound."""
    if not labels:
        return 0
    want = {k: str(v) for k, v in labels.items()}
    with _metrics_lock:
        doomed = []
        for n in _metrics:
            _, nl = split_labeled_name(n)
            if nl and all(nl.get(k) == v for k, v in want.items()):
                doomed.append(n)
        for n in doomed:
            _metrics.pop(n, None)
    return len(doomed)


def gauge(name):
    return _get_metric(name, Gauge)


def timer(name):
    return _get_metric(name, Timer)


def histogram(name):
    return _get_metric(name, Histogram)


# -- hot-path helpers: single flag check, no allocation when off -----------

def inc(name, n=1):
    if _metrics_on:
        counter(name).inc(n)


def set_gauge(name, value):
    if _metrics_on:
        gauge(name).set(value)


def observe(name, seconds):
    if _metrics_on:
        timer(name).observe(seconds)


def observe_hist(name, value, exemplar=None):
    if _metrics_on:
        histogram(name).observe(value, exemplar)


# ---------------------------------------------------------------------------
# Counters computed on the device
# ---------------------------------------------------------------------------

# What a compiled step counts itself (an operator's auxiliary states: the
# assignments a SparseExperts layer routed) stays on the device step after
# step and becomes counters here only where the fit loop waits for the
# device anyway: the metric drain (``metric.EvalMetric._drain_device``)
# takes every source's arrays into its one batched sync and then lets the
# source write its counters.  No step gains a host sync.
_device_sources = []              # weak references to bound methods


def add_device_source(method):
    """``method()`` returns ``(arrays, apply)`` or None: device arrays
    for the drain's sync, and the function that reads them afterwards."""
    import weakref
    _device_sources.append(weakref.WeakMethod(method))


def take_device_sources():
    if not _metrics_on or not _device_sources:
        return ()
    taken, alive = [], []
    for ref in _device_sources:
        method = ref()
        if method is None:
            continue
        alive.append(ref)
        got = method()
        if got is not None:
            taken.append(got)
    _device_sources[:] = alive
    return taken


# ---------------------------------------------------------------------------
# Unified decision events (the control planes' one logging API)
# ---------------------------------------------------------------------------

# every subsystem that ACTS — the serving autoscaler's scale/brownout
# ladder, the supervisor's quarantine/replay, elastic membership
# repairs, health skip/abort, fault-plan arming, chronicle anomalies —
# logs its actions through decision(), so one merged timeline
# (tools/timeline.py) can order them against each other after the fact.
DECISION_RING = 512

_decisions = []                  # bounded ring of decision events
_decision_lock = threading.Lock()
_decision_seq = {}               # subsystem -> last seq issued
_decision_last_t = {}            # subsystem -> last wall time stamped
_decision_sinks = []             # callables fed every event (chronicle)


def decision(subsystem, action, reason='', severity='info', **fields):
    """Record one typed control-plane decision event and return it.

    The event is ``{'t', 'subsystem', 'action', 'reason', 'severity',
    'seq', **fields}``: ``seq`` is per-subsystem monotonic and ``t`` is
    stamped under the same lock, clamped non-decreasing per subsystem —
    so within one subsystem LANE, (seq, t) order agree by construction
    (``tools/check_trace.py`` / ``tools/timeline.py --strict`` validate
    exactly that invariant on dumps).  Always recorded into the bounded
    in-memory ring (decisions are rare, control-plane-rate events — the
    perfwatch zero-overhead contract applies to hot paths, not these);
    counters ride only under metrics, the trace instant only under
    profiling, and registered sinks (the chronicle journal) are fed
    best-effort — a broken sink cannot fail the decision site."""
    subsystem = str(subsystem)
    with _decision_lock:
        seq = _decision_seq.get(subsystem, 0) + 1
        _decision_seq[subsystem] = seq
        t = time.time()
        last = _decision_last_t.get(subsystem)
        if last is not None and t < last:
            t = last              # wall clock stepped back (NTP): clamp
        _decision_last_t[subsystem] = t
        ev = {'t': t, 'subsystem': subsystem, 'action': str(action),
              'reason': str(reason), 'severity': str(severity),
              'seq': seq}
        for k, v in fields.items():
            if k not in ev:
                ev[k] = v
        _decisions.append(ev)
        del _decisions[:-DECISION_RING]
        sinks = list(_decision_sinks)
    if _metrics_on:
        inc('decision.events')
        inc('decision.%s' % subsystem)
    if _profile_on:
        args = {'subsystem': subsystem, 'action': ev['action'],
                'reason': ev['reason'], 'seq': seq}
        for k in ('model', 'replica', 'rank', 'series'):
            if k in ev:
                args[k] = ev[k]
        record_complete('decision.%s.%s' % (subsystem, ev['action']),
                        int(t * 1e6), 0, cat='decision', args=args)
    for sink in sinks:
        try:
            sink(ev)
        except Exception:
            pass
    return ev


def recent_decisions(limit=None, subsystem=None):
    """The newest decision events (oldest-first), optionally filtered
    by subsystem — the flight recorder's and timeline's read path."""
    with _decision_lock:
        evs = list(_decisions)
    if subsystem is not None:
        evs = [e for e in evs if e.get('subsystem') == subsystem]
    if limit is not None:
        evs = evs[-int(limit):]
    return evs


def on_decision(fn):
    """Register ``fn(event)`` to be called for every decision event
    (idempotent).  Sinks must be fast and never raise into the
    decision site (exceptions are swallowed)."""
    with _decision_lock:
        if fn not in _decision_sinks:
            _decision_sinks.append(fn)


def remove_decision_sink(fn):
    with _decision_lock:
        if fn in _decision_sinks:
            _decision_sinks.remove(fn)


# Per-thread trace-counter redirect: the compile_cache warmup pool
# pre-traces programs ahead of time — those traces must not inflate the
# hot-path counters (executor.xla_traces), so the warmup thread routes
# them to compile.warmup_traces for the duration of its lowering.
_trace_tls = threading.local()


class _TraceRedirectCtx(object):
    __slots__ = ('name', '_prev')

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self._prev = getattr(_trace_tls, 'name', None)
        _trace_tls.name = self.name
        return self

    def __exit__(self, *exc):
        _trace_tls.name = self._prev
        return False


def trace_redirect(name):
    """Route :func:`count_trace` increments on THIS thread to ``name``
    while the context is active (nests; restores the previous target)."""
    return _TraceRedirectCtx(name)


def count_trace(name):
    """Count one jit trace: the framework-wide ``compile.traces``
    counter plus the site counter ``name`` (redirect-aware — see
    :func:`trace_redirect`)."""
    if not _metrics_on:
        return
    inc('compile.traces')
    inc(getattr(_trace_tls, 'name', None) or name)


def count_traces(name, fn):
    """Wrap ``fn`` for ``jax.jit(count_traces(name, fn))``: jit calls
    the Python callable only while TRACING (cached executions skip it),
    so the counter fires per actual trace — catching shape-driven
    retraces that a framework-level program cache reports as hits."""
    @functools.wraps(fn)
    def wrapper(*a, **kw):
        count_trace(name)
        return fn(*a, **kw)
    return wrapper


def timed(name):
    """Context-manager timer (safe to nest and share across threads),
    no-op when metrics are off."""
    if not _metrics_on:
        return _NULL_SPAN
    return _TimedCtx(timer(name))


def reset_metrics():
    with _metrics_lock:
        _metrics.clear()


def device_memory_stats():
    """Device memory stats of the first local device (bytes in use, peak,
    pool limit — whatever the backend exposes).  Returns {} when the
    backend reports none (CPU) or is not live; never initializes a
    backend by itself — merely importing jax is not enough, since
    ``jax.local_devices()`` on an uninitialized backend would trigger
    initialization."""
    if 'jax' not in sys.modules:
        return {}
    try:
        import jax
        from jax._src import xla_bridge as _xb
        if not getattr(_xb, '_backends', None):
            return {}
        stats = jax.local_devices()[0].memory_stats()
        return dict(stats) if stats else {}
    except Exception:
        return {}


def metrics_snapshot():
    """The whole registry as one JSON-serializable dict.  Field reads
    stay under the registry lock so a concurrent observe()/inc() cannot
    tear a Timer's total/count pair mid-snapshot."""
    snap = {'counters': {}, 'gauges': {}, 'timers': {}}
    hists = []
    with _metrics_lock:
        for m in list(_metrics.values()):
            if isinstance(m, Counter):
                snap['counters'][m.name] = m.value
            elif isinstance(m, Gauge):
                snap['gauges'][m.name] = m.value
            elif isinstance(m, Timer):
                snap['timers'][m.name] = {'total_sec': m.total,
                                          'count': m.count,
                                          'avg_sec': m.avg}
            elif isinstance(m, Histogram):
                # snapshot outside the registry lock: each
                # Histogram takes it for its own read
                hists.append(m)
    if hists:
        snap['histograms'] = {m.name: m.snapshot() for m in hists}
    mem = device_memory_stats()
    if mem:
        snap['device_memory'] = mem
    return snap


def dump_metrics(path):
    snap = metrics_snapshot()
    with open(path, 'w') as f:
        json.dump(snap, f, indent=1, sort_keys=True)
    return snap


# ---------------------------------------------------------------------------
# Prometheus text exposition
# ---------------------------------------------------------------------------

_PROM_BAD = re.compile(r'[^a-zA-Z0-9_:]')


def _prom_name(name, suffix=''):
    """Sanitize a registry metric name into a legal Prometheus metric
    name: ``metric.host_syncs`` -> ``mxtpu_metric_host_syncs``."""
    s = _PROM_BAD.sub('_', str(name))
    if s and s[0].isdigit():
        s = '_' + s
    return 'mxtpu_' + s + suffix


def _prom_value(v):
    try:
        f = float(v)
    except (TypeError, ValueError):
        return '0'
    if f != f:
        return 'NaN'
    if f == float('inf'):
        return '+Inf'
    if f == float('-inf'):
        return '-Inf'
    return str(int(f)) if f.is_integer() else repr(f)


def split_labeled_name(name):
    """Parse a registry metric name of the form
    ``base|key=value,key2=value2`` into ``(base, labels-dict)``.

    This is the labeled-series convention of the registry: the registry
    itself is a flat name->metric map (labels are not first-class), so
    planes that need per-entity attribution (the serving fleet's
    ``serving.execute_secs|model=clf,replica=1``) encode the label set
    into the name after a ``|``.  :func:`render_prometheus` splits it
    back out into REAL Prometheus labels, so a hot replica is a label
    match away instead of averaged into the model-level series.  Names
    without a ``|`` return ``(name, None)`` unchanged."""
    if '|' not in str(name):
        return name, None
    base, _, rest = str(name).partition('|')
    labels = {}
    for part in rest.split(','):
        k, eq, v = part.partition('=')
        if eq and k:
            labels[k] = v
    return base, (labels or None)


def render_prometheus(snapshot=None, labels=None, seen_types=None,
                      timestamp_ms=None):
    """Render a metrics snapshot (default: the live registry) as
    Prometheus text exposition.  Counters become ``<name>_total``,
    timers expand to ``<name>_seconds_total`` + ``<name>_calls_total``;
    names are sanitized to the Prometheus charset.  Registry names
    carrying a ``|key=value`` label section (see
    :func:`split_labeled_name`) emit as the base metric with those
    labels attached, so labeled series (per-replica serving histograms)
    merge under ONE ``# TYPE`` family.  ``labels`` adds a label set to
    every sample (the kv server tags per-rank series with ``rank="N"``;
    caller labels win on a key collision); pass one shared
    ``seen_types`` set across calls when concatenating several
    snapshots so each ``# TYPE`` line is emitted exactly once.

    ``timestamp_ms`` (default off) appends a millisecond timestamp to
    every SAMPLE line (``# TYPE`` comments never carry one) so scraped
    series align with the chronicle journal's wall clock: pass True to
    stamp render time, or an explicit epoch-milliseconds integer (the
    kv server stamps the merge instant, so every rank's samples in one
    exposition carry the same timestamp)."""
    snap = metrics_snapshot() if snapshot is None else snapshot
    seen = seen_types if seen_types is not None else set()
    if timestamp_ms is True:
        timestamp_ms = int(time.time() * 1000)
    stamp = '' if not timestamp_ms else ' %d' % int(timestamp_ms)

    def labstr(d):
        if not d:
            return ''
        # the Prometheus text format's label-value escapes: backslash,
        # double quote, and newline (an unescaped newline would split
        # the sample line and fail the whole scrape)
        return '{%s}' % ','.join(
            '%s="%s"' % (k, str(v).replace('\\', '\\\\')
                         .replace('"', '\\"').replace('\n', '\\n'))
            for k, v in sorted(d.items()))

    def merged(name_labels):
        if not name_labels:
            return labels
        out = dict(name_labels)
        if labels:
            out.update(labels)
        return out

    lines = []

    def emit(k, typ, value, suffix=''):
        base, name_labels = split_labeled_name(k)
        name = _prom_name(base, suffix)
        if name not in seen:
            seen.add(name)
            lines.append('# TYPE %s %s' % (name, typ))
        lines.append('%s%s %s%s' % (name, labstr(merged(name_labels)),
                                    _prom_value(value), stamp))

    for k, v in sorted((snap.get('counters') or {}).items()):
        emit(k, 'counter', v, '_total')
    for k, v in sorted((snap.get('gauges') or {}).items()):
        emit(k, 'gauge', v)
    for k, t in sorted((snap.get('timers') or {}).items()):
        t = t or {}
        emit(k, 'counter', t.get('total_sec', 0.0), '_seconds_total')
        emit(k, 'counter', t.get('count', 0), '_calls_total')
    for k, h in sorted((snap.get('histograms') or {}).items()):
        h = h or {}
        base_name, name_labels = split_labeled_name(k)
        name = _prom_name(base_name)
        if name not in seen:
            seen.add(name)
            lines.append('# TYPE %s histogram' % name)
        # cumulative le= buckets; a +Inf bucket always closes the set
        # (Prometheus requires it even when no observation overflowed)
        series = merged(name_labels)
        lab = labstr(series)
        base = dict(series) if series else {}
        buckets = list(h.get('buckets') or [])
        if not buckets or buckets[-1][0] != '+Inf':
            buckets.append(['+Inf', int(h.get('count', 0))])
        # last request id per bucket (the request-attribution plane's
        # exemplars) in the OpenMetrics exemplar syntax — a bad le=
        # bucket links straight to a concrete request postmortem.
        # Exemplar-free histograms render byte-identically to before.
        exemplars = {}
        for ex in h.get('exemplars') or []:
            try:
                le, rid, val = ex
            except (TypeError, ValueError):
                continue
            key = le if isinstance(le, str) else _prom_value(le)
            exemplars[key] = (rid, val)
        for le, cum in buckets:
            bl = dict(base)
            bl['le'] = le if isinstance(le, str) else _prom_value(le)
            ex = exemplars.get(bl['le'])
            tail = '' if ex is None else \
                ' # {request_id="%s"} %s' % (ex[0], _prom_value(ex[1]))
            lines.append('%s_bucket%s %d%s%s'
                         % (name, labstr(bl), cum, stamp, tail))
        lines.append('%s_sum%s %s%s' % (name, lab,
                                        _prom_value(h.get('sum', 0.0)),
                                        stamp))
        lines.append('%s_count%s %s%s' % (name, lab,
                                          _prom_value(h.get('count', 0)),
                                          stamp))
    return '\n'.join(lines) + '\n' if lines else ''


_refresh_from_env()
