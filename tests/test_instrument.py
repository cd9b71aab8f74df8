"""Tier-1 tests for mxnet_tpu.instrument — the unified tracing/metrics
layer (ISSUE 1) — and the profiler.py compatibility shim over it.

Covers span nesting, Chrome-trace schema validity (via
tools/check_trace.py, so the standalone validator stays exercised),
counter/gauge/timer arithmetic, metrics snapshot round-trip, the
disabled path producing zero events, the off-path overhead guard, the
multi-thread tid regression (old profiler.py hardcoded pid=0/tid=0),
and an end-to-end profiled Module.fit.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, instrument, profiler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECK_TRACE = os.path.join(REPO, 'tools', 'check_trace.py')

sys.path.insert(0, os.path.join(REPO, 'tools'))
import check_trace  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_instrument_state():
    """Flags are process-global: leave them as found, drop any events or
    metrics a test recorded so the rest of the suite is unaffected."""
    prof, met = instrument.profiling_enabled(), instrument.metrics_enabled()
    instrument.clear_trace()
    instrument.reset_metrics()
    yield
    instrument.set_profiling(prof)
    instrument.set_metrics(met)
    instrument.clear_trace()
    instrument.reset_metrics()


def _events(doc):
    return [e for e in doc['traceEvents'] if e.get('ph') != 'M']


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

def test_span_nesting(tmp_path):
    instrument.set_profiling(True)
    with instrument.span('outer', cat='test'):
        time.sleep(0.002)
        with instrument.span('inner', cat='test', args={'k': 1}):
            time.sleep(0.001)
    path = str(tmp_path / 'trace.json')
    n = instrument.dump_trace(path)
    assert n == 2
    with open(path) as f:
        by_name = {e['name']: e for e in _events(json.load(f))}
    outer, inner = by_name['outer'], by_name['inner']
    # inner lies within outer on the same thread — that containment is
    # exactly what makes Perfetto stack them
    assert inner['tid'] == outer['tid']
    assert inner['ts'] >= outer['ts']
    assert inner['ts'] + inner['dur'] <= outer['ts'] + outer['dur']
    assert inner['dur'] < outer['dur']
    assert inner['args'] == {'k': 1}


def test_instrumented_decorator():
    calls = []

    @instrument.instrumented(cat='test')
    def work(x):
        calls.append(x)
        return x + 1

    assert work(1) == 2                      # disabled: plain call
    assert instrument.trace_events() == []
    instrument.set_profiling(True)
    assert work(2) == 3
    events = instrument.trace_events()
    assert len(events) == 1
    assert events[0]['name'].endswith('work')
    assert calls == [1, 2]


def test_trace_schema_and_validator(tmp_path):
    instrument.set_profiling(True)

    def worker():
        with instrument.span('thread_work', cat='test'):
            time.sleep(0.001)

    t = threading.Thread(target=worker, name='producer')
    with instrument.span('main_work', cat='test'):
        t.start()
        t.join()
    good = str(tmp_path / 'good.json')
    instrument.dump_trace(good)

    with open(good) as f:
        doc = json.load(f)
    assert doc['displayTimeUnit'] == 'ms'
    for e in _events(doc):
        for field in ('name', 'ph', 'ts', 'pid', 'tid'):
            assert field in e, (field, e)
    meta = [e for e in doc['traceEvents'] if e.get('ph') == 'M']
    names = {(e['name'], e['args']['name']) for e in meta}
    assert ('process_name', 'mxnet_tpu') in names
    assert ('thread_name', 'producer') in names

    # the standalone validator agrees, both in-process and as the CLI
    assert check_trace.validate_file(good) == []
    assert subprocess.call([sys.executable, CHECK_TRACE, good]) == 0

    bad = str(tmp_path / 'bad.json')
    with open(bad, 'w') as f:
        json.dump({'traceEvents': [{'ph': 'X', 'ts': 0}]}, f)
    assert check_trace.validate_file(bad)
    assert subprocess.call(
        [sys.executable, CHECK_TRACE, bad],
        stderr=subprocess.DEVNULL) != 0
    assert subprocess.call(
        [sys.executable, CHECK_TRACE, str(tmp_path / 'absent.json')],
        stderr=subprocess.DEVNULL) != 0


def test_profiler_shim_distinct_tids(tmp_path):
    """Regression for the old profiler.py, which hardcoded pid=0/tid=0 so
    every thread collapsed into one Perfetto lane."""
    path = str(tmp_path / 'profile.json')
    profiler.profiler_set_config(filename=path)

    def worker():
        with profiler.Scope('worker_step'):
            time.sleep(0.001)

    t = threading.Thread(target=worker)
    with profiler.Scope('main_step'):
        t.start()
        t.join()
    profiler.dump_profile()

    with open(path) as f:
        events = _events(json.load(f))
    assert {e['name'] for e in events} == {'worker_step', 'main_step'}
    assert len({e['tid'] for e in events}) == 2
    assert all(e['pid'] == os.getpid() for e in events)
    assert check_trace.validate_file(path) == []


def test_profiler_run_stop_restores_flags(tmp_path):
    """A profiler run/stop cycle must not leave the span tracer OR the
    metrics registry (forced on by set_profiling) enabled afterwards."""
    profiler.profiler_set_config(filename=str(tmp_path / 'p.json'))
    assert not instrument.profiling_enabled()
    assert not instrument.metrics_enabled()
    profiler.profiler_set_state('run')
    assert instrument.profiling_enabled()
    profiler.profiler_set_state('stop')
    assert not instrument.profiling_enabled()
    assert not instrument.metrics_enabled()


def test_profiler_start_failure_propagates(tmp_path, monkeypatch):
    """profiler_set_state('run') asks for a device trace: when
    jax.profiler.start_trace fails the caller hears of it, and no
    half-started run is left behind."""
    import jax

    def refuse(log_dir):
        raise RuntimeError('trace refused: %s' % log_dir)

    monkeypatch.setattr(jax.profiler, 'start_trace', refuse)
    profiler.profiler_set_config(filename=str(tmp_path / 'p.json'))
    with pytest.raises(RuntimeError, match='trace refused'):
        profiler.profiler_set_state('run')
    assert not instrument.profiling_enabled()
    profiler.profiler_set_state('stop')      # nothing is running: a no-op


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

def test_counter_gauge_timer_arithmetic():
    instrument.set_metrics(True)
    instrument.inc('c')
    instrument.inc('c', 41)
    assert instrument.counter('c').value == 42
    instrument.set_gauge('g', 2.5)
    instrument.set_gauge('g', 7.5)
    assert instrument.gauge('g').value == 7.5
    instrument.observe('t', 1.0)
    instrument.observe('t', 3.0)
    t = instrument.timer('t')
    assert t.count == 2 and t.total == 4.0 and t.avg == 2.0
    with instrument.timed('t'):
        time.sleep(0.001)
    assert t.count == 3 and t.total > 4.0
    with instrument.timed('t'):        # nested same-name regions must
        with instrument.timed('t'):    # not clobber each other's start
            time.sleep(0.001)
    assert t.count == 5
    with pytest.raises(TypeError):
        instrument.gauge('c')          # name registered as a Counter


def test_metrics_snapshot_roundtrip(tmp_path):
    instrument.set_metrics(True)
    instrument.inc('steps', 3)
    instrument.set_gauge('ips', 123.5)
    instrument.observe('phase', 0.25)
    snap = instrument.metrics_snapshot()
    path = str(tmp_path / 'metrics.json')
    dumped = instrument.dump_metrics(path)
    with open(path) as f:
        loaded = json.load(f)
    assert loaded == json.loads(json.dumps(dumped)) == json.loads(
        json.dumps(snap))
    assert loaded['counters']['steps'] == 3
    assert loaded['gauges']['ips'] == 123.5
    assert loaded['timers']['phase'] == {
        'total_sec': 0.25, 'count': 1, 'avg_sec': 0.25}


def test_histogram_buckets_and_quantiles():
    """The bounded-memory histogram (ISSUE 6 satellite): fixed
    log-scale buckets, accurate-enough quantiles, cumulative snapshot."""
    instrument.set_metrics(True)
    rng = np.random.RandomState(0)
    for v in rng.uniform(0.0, 0.1, size=5000):
        instrument.observe_hist('lat', v)
    h = instrument.histogram('lat')
    # uniform[0, 0.1]: p50 ~ 0.05, p99 ~ 0.099; log buckets at quarter
    # decades bound the estimate error well inside 2x
    assert 0.03 < h.quantile(0.50) < 0.08
    assert 0.07 < h.quantile(0.99) <= 0.12
    assert h.count == 5000 and abs(h.sum - 0.05 * 5000) < 25
    # memory is bounded: the counts array never grows with samples
    assert len(h.counts) == len(instrument.HIST_EDGES) + 1
    snap = instrument.metrics_snapshot()['histograms']['lat']
    assert snap['count'] == 5000
    assert snap['p50'] == h.quantile(0.50)
    # buckets are cumulative and monotonic
    cums = [c for _, c in snap['buckets']]
    assert cums == sorted(cums) and cums[-1] == 5000
    with pytest.raises(TypeError):
        instrument.counter('lat')      # name registered as a Histogram


def test_histogram_overflow_and_empty():
    instrument.set_metrics(True)
    instrument.observe_hist('big', 1e6)     # beyond the last edge
    h = instrument.histogram('big')
    assert h.counts[-1] == 1 and h.count == 1
    snap = h.snapshot()
    assert snap['buckets'] == [['+Inf', 1]]
    assert instrument.histogram('none').quantile(0.99) == 0.0


def test_histogram_prometheus_exposition():
    instrument.set_metrics(True)
    for v in (0.001, 0.01, 0.1):
        instrument.observe_hist('serving.e2e_secs', v)
    prom = instrument.render_prometheus(labels={'rank': 3})
    lines = prom.splitlines()
    assert '# TYPE mxtpu_serving_e2e_secs histogram' in lines
    buckets = [l for l in lines
               if l.startswith('mxtpu_serving_e2e_secs_bucket')]
    # every bucket line carries BOTH the le= and the shared labels,
    # and the +Inf bucket closes the set at the total count
    assert buckets and all('rank="3"' in l and 'le="' in l
                           for l in buckets)
    assert buckets[-1] == \
        'mxtpu_serving_e2e_secs_bucket{le="+Inf",rank="3"} 3'
    assert 'mxtpu_serving_e2e_secs_count{rank="3"} 3' in lines
    assert any(l.startswith('mxtpu_serving_e2e_secs_sum{rank="3"}')
               for l in lines)
    # the generic validator still accepts a snapshot with histograms
    # in a shared-seen_types two-snapshot concat (the kv server path)
    seen = set()
    a = instrument.render_prometheus(seen_types=seen)
    b = instrument.render_prometheus(seen_types=seen)
    assert a.count('# TYPE mxtpu_serving_e2e_secs histogram') == 1
    assert b.count('# TYPE') == 0


def test_set_profiling_off_releases_implied_metrics():
    """set_profiling(True) implies metrics; set_profiling(False) must
    release them again — but never clobber an explicit set_metrics."""
    instrument.set_profiling(False)
    instrument.set_metrics(False)
    instrument.set_profiling(True)
    assert instrument.metrics_enabled()       # implied
    instrument.set_profiling(False)
    assert not instrument.metrics_enabled()   # released
    instrument.set_metrics(True)              # explicit
    instrument.set_profiling(True)
    instrument.set_profiling(False)
    assert instrument.metrics_enabled()       # explicit survives


def test_io_batches_counted_once_through_wrappers():
    """Each delivered batch bumps io.batches exactly once, through 1:1
    wrappers (ResizeIter) and through a merging PrefetchingIter over
    MULTIPLE inner iterators (n leaf batches -> one delivered batch)."""
    instrument.set_metrics(True)
    X = np.zeros((32, 4), np.float32)
    y = np.zeros(32, np.float32)
    it = mx.io.ResizeIter(mx.io.NDArrayIter(X, y, batch_size=8), size=4)
    assert sum(1 for _ in it) == 4
    assert instrument.counter('io.batches').value == 4

    instrument.reset_metrics()
    pre = mx.io.PrefetchingIter(
        [mx.io.NDArrayIter(X, y, batch_size=8),
         mx.io.NDArrayIter({'data2': X}, None, batch_size=8)])
    assert sum(1 for _ in pre) == 4
    assert instrument.counter('io.batches').value == 4


def test_env_var_registration(monkeypatch):
    assert config.get('MXTPU_PROFILE') is False
    assert config.get('MXTPU_METRICS') is False
    monkeypatch.setenv('MXTPU_PROFILE', '1')
    instrument._refresh_from_env()
    assert instrument.profiling_enabled()
    assert instrument.metrics_enabled()       # profiling implies metrics
    monkeypatch.setenv('MXTPU_PROFILE', '0')
    monkeypatch.setenv('MXTPU_METRICS', '1')
    instrument._refresh_from_env()
    assert not instrument.profiling_enabled()
    assert instrument.metrics_enabled()
    monkeypatch.delenv('MXTPU_METRICS')
    instrument._refresh_from_env()
    assert not instrument.metrics_enabled()


# ---------------------------------------------------------------------------
# Disabled path
# ---------------------------------------------------------------------------

def test_overflow_drops_counted_once(tmp_path, monkeypatch):
    """Events past MAX_EVENTS_PER_THREAD are counted into the dump as
    mxtpuDroppedEvents — each drop reported exactly once across dumps."""
    instrument.set_profiling(True)
    monkeypatch.setattr(instrument, 'MAX_EVENTS_PER_THREAD', 2)
    for i in range(5):
        with instrument.span('e%d' % i):
            pass
    path = str(tmp_path / 'overflow.json')
    assert instrument.dump_trace(path) == 2
    with open(path) as f:
        assert json.load(f)['mxtpuDroppedEvents'] == 3
    with instrument.span('later'):     # drained: room again, delta reset
        pass
    assert instrument.dump_trace(path) == 1
    with open(path) as f:
        assert 'mxtpuDroppedEvents' not in json.load(f)


def test_disabled_path_zero_events():
    assert not instrument.profiling_enabled()
    with instrument.span('never', args={'x': 1}):
        pass
    instrument.inc('never')
    instrument.set_gauge('never_g', 1.0)
    instrument.observe('never_t', 1.0)
    with instrument.timed('never_t2'):
        pass
    assert instrument.trace_events() == []
    snap = instrument.metrics_snapshot()
    assert snap['counters'] == {} and snap['gauges'] == {}
    assert snap['timers'] == {}


def test_hist_span_sinks_one_region_three_ways(monkeypatch):
    """hist_span: a histogram sample, a Chrome span under profiling, and
    a jax.profiler annotation named ``mxtpu.`` + the series that is
    entered first and left last; with ``step_num`` a step annotation;
    a cancelled region leaves a mark in the profiler and nothing else."""
    import jax
    seen = []

    class Fake(object):
        def __init__(self, name, **kwargs):
            self.name, self.kwargs = name, kwargs

        def __enter__(self):
            seen.append(('enter', self.name, time.time_ns()))

        def __exit__(self, *exc):
            seen.append(('exit', self.name, time.time_ns()))

        def set_metadata(self, **kwargs):
            seen.append(('metadata', self.name, kwargs))

    class FakeStep(Fake):
        def __init__(self, name, **kwargs):
            super().__init__('step:' + name, **kwargs)
            seen.append(('step_num', kwargs['step_num']))

    monkeypatch.setattr(jax.profiler, 'TraceAnnotation', Fake)
    monkeypatch.setattr(jax.profiler, 'StepTraceAnnotation', FakeStep)
    instrument.set_profiling(True)
    with instrument.hist_span('perf.phase.seam_t'):
        time.sleep(0.002)
    (_, name, entered), (_, _, left) = seen
    assert name == 'mxtpu.perf.phase.seam_t'
    event, = [e for e in instrument.trace_events()
              if e['name'] == 'perf.phase.seam_t']
    assert entered // 1000 <= event['ts']
    assert event['ts'] + event['dur'] <= left // 1000 + 1
    hist = instrument.metrics_snapshot()['histograms']['perf.phase.seam_t']
    assert hist['count'] == 1 and hist['sum'] >= 0.002
    del seen[:]
    with instrument.hist_span('perf.root_t', cat='fit', step_num=41) as root:
        root.cancel()
    assert seen[0] == ('step_num', 41)
    assert [s[:2] for s in seen[1:]] == [
        ('enter', 'step:mxtpu.perf.root_t'),
        ('metadata', 'step:mxtpu.perf.root_t'),
        ('exit', 'step:mxtpu.perf.root_t')]
    assert seen[2][2] == {'cancelled': 1}
    assert 'perf.root_t' not in instrument.metrics_snapshot()['histograms']
    assert not [e for e in instrument.trace_events()
                if e['name'] == 'perf.root_t']
    # the shared no-op takes the same call
    assert instrument.NULL_CTX.__enter__().cancel() is None


def test_disabled_span_overhead_guard():
    """Off-path span entry must stay allocation-free.  The baseline is
    an inlined ideal zero-overhead context manager — a flag check
    returning a shared no-op instance — because against a literally
    empty loop the with-statement's three interpreter calls alone exceed
    2x and the guard would measure CPython, not us.  Against this floor,
    today's off-path sits near 1x while buffering/allocating versions
    measure 3-7x, so < 2x pins the property the ISSUE wants: no future
    PR may make the off path allocate."""
    class _Floor(object):
        __slots__ = ()

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    _floor = _Floor()
    _flag = False

    def floor_span(name, cat='host', args=None):
        if not _flag:
            return _floor

    n = 10000

    def timeit(fn):
        best = float('inf')
        for _ in range(7):
            t0 = time.perf_counter()
            for _i in range(n):
                with fn('bench'):
                    pass
            best = min(best, time.perf_counter() - t0)
        return best

    assert not instrument.profiling_enabled()
    ratio = min(timeit(instrument.span) / timeit(floor_span)
                for _ in range(3))       # best-of-3 damps CI-box noise
    assert ratio < 2.0, 'disabled span() is %.2fx the no-op floor' % ratio
    assert instrument.trace_events() == []


# ---------------------------------------------------------------------------
# End to end: profiled fit
# ---------------------------------------------------------------------------

def test_profiled_fit_trace_and_metrics(tmp_path):
    """The acceptance scenario: a profiled small Module.fit yields a
    valid Chrome trace containing executor, sync, io, and epoch/batch
    spans, and a metrics snapshot with samples/sec and retrace
    counters."""
    from mxnet_tpu import sym

    data = sym.Variable('data')
    fc1 = sym.FullyConnected(data, num_hidden=16, name='fc1')
    act = sym.Activation(fc1, act_type='relu')
    fc2 = sym.FullyConnected(act, num_hidden=4, name='fc2')
    net = sym.SoftmaxOutput(fc2, name='softmax')

    rng = np.random.RandomState(7)
    X = rng.randn(64, 8).astype(np.float32)
    y = (rng.rand(64) * 4).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=16)

    instrument.set_profiling(True)
    mod = mx.module.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=2, optimizer_params={'learning_rate': 0.1})

    path = str(tmp_path / 'fit_trace.json')
    assert instrument.dump_trace(path) > 0
    assert check_trace.validate_file(path) == []
    assert subprocess.call([sys.executable, CHECK_TRACE, path]) == 0

    with open(path) as f:
        events = _events(json.load(f))
    names = {e['name'] for e in events}
    cats = {e.get('cat') for e in events}
    assert 'executor' in cats                  # forward/backward or fused
    assert 'engine.sync' in names              # the WaitForVar analogue
    assert 'io.next' in names
    assert 'fit.epoch[0]' in names and 'fit.epoch[1]' in names
    assert 'fit.batch' in names
    # epoch span contains its batches
    epoch0 = next(e for e in events if e['name'] == 'fit.epoch[0]')
    batches = [e for e in events if e['name'] == 'fit.batch']
    assert len(batches) == 8                   # 4 per epoch x 2 epochs
    assert any(epoch0['ts'] <= b['ts'] and
               b['ts'] + b['dur'] <= epoch0['ts'] + epoch0['dur']
               for b in batches)

    snap = instrument.metrics_snapshot()
    assert snap['gauges']['fit.samples_per_sec'] > 0
    assert snap['counters']['fit.samples'] == 128
    assert snap['counters']['fit.batches'] == 8
    assert snap['counters']['io.batches'] == 8
    assert 'executor.retraces' in snap['counters']
    assert snap['counters']['executor.cache_hits'] >= \
        snap['counters']['executor.retraces']
    # counted at trace time inside the jitted step; uniform shapes here,
    # so jax traced exactly as often as the framework cache missed
    assert snap['counters']['executor.xla_traces'] == \
        snap['counters']['executor.retraces']
    assert snap['timers']['fit.step']['count'] == 8
    assert snap['timers']['fit.epoch']['count'] == 2


def test_hist_delta_windowed_view():
    """Windowed histogram snapshots (ISSUE 15 satellite): the delta of
    two cumulative snapshots describes ONLY the observations between
    them — fast recent latency is not hidden by a slow lifetime."""
    instrument.set_metrics(True)
    for _ in range(200):
        instrument.observe_hist('win', 1.0)       # slow history
    prev = instrument.histogram('win').snapshot()
    for _ in range(100):
        instrument.observe_hist('win', 0.001)     # fast recent window
    cur = instrument.histogram('win').snapshot()
    d = instrument.hist_delta(cur, prev)
    assert d['count'] == 100
    assert abs(d['sum'] - 0.1) < 1e-6
    # the window sees only the fast samples; the cumulative view is
    # still dominated by the slow history
    assert d['p99'] < 0.01 < 0.5 < cur['p99']
    # prev None reproduces the cumulative form through the same math
    full = instrument.hist_delta(cur, None)
    assert full['count'] == cur['count']
    # a reset between snapshots clamps to empty, never negative
    assert instrument.hist_delta(prev, cur)['count'] == 0


def test_hist_merge_label_merged_view():
    instrument.set_metrics(True)
    for v in (0.001, 0.002):
        instrument.observe_hist('m.lat|replica=0', v)
    for v in (1.0, 2.0):
        instrument.observe_hist('m.lat|replica=1', v)
    s0 = instrument.histogram('m.lat|replica=0').snapshot()
    s1 = instrument.histogram('m.lat|replica=1').snapshot()
    merged = instrument.hist_merge([s0, s1])
    assert merged['count'] == 4
    assert abs(merged['sum'] - 3.003) < 1e-6
    # the merged p99 lands in the slow replica's range: a hot replica
    # is visible in the model-level view, not averaged to the floor
    assert merged['p99'] > 0.5
    assert instrument.hist_merge([])['count'] == 0


def test_histogram_window_advances_per_consumer():
    instrument.set_metrics(True)
    win = instrument.HistogramWindow()
    other = instrument.HistogramWindow()
    instrument.observe_hist('w.lat', 0.01)
    assert win.delta('w.lat')['count'] == 1
    assert win.delta('w.lat')['count'] == 0      # window advanced
    # a second consumer holds its OWN window
    assert other.delta('w.lat')['count'] == 1
    instrument.observe_hist('w.lat|model=a,replica=0', 0.01)
    instrument.observe_hist('w.lat|model=a,replica=1', 0.02)
    names = win.peek_names('w.lat|')
    assert names == ['w.lat|model=a,replica=0',
                     'w.lat|model=a,replica=1']
    assert win.merged_delta(names)['count'] == 2
    # missing histogram: empty window, no registry pollution
    assert win.delta('w.nothere')['count'] == 0
    assert 'w.nothere' not in instrument.metrics_snapshot().get(
        'histograms', {})


def test_labeled_names_in_prometheus_exposition():
    """Registry names carrying a |key=value section render as REAL
    Prometheus labels under one # TYPE family (the serving fleet's
    per-replica attribution)."""
    instrument.set_metrics(True)
    instrument.inc('srv.flushes|model=clf,replica=0', 3)
    instrument.inc('srv.flushes|model=clf,replica=1', 5)
    instrument.observe_hist('srv.lat|model=clf,replica=1', 0.01)
    instrument.set_gauge('srv.replicas|model=clf', 2)
    prom = instrument.render_prometheus(labels={'rank': 0})
    lines = prom.splitlines()
    assert 'mxtpu_srv_flushes_total{model="clf",rank="0",replica="0"} 3' \
        in lines
    assert 'mxtpu_srv_flushes_total{model="clf",rank="0",replica="1"} 5' \
        in lines
    # one TYPE line for the whole labeled family
    assert prom.count('# TYPE mxtpu_srv_flushes_total counter') == 1
    assert 'mxtpu_srv_replicas{model="clf",rank="0"} 2' in lines
    hb = [l for l in lines if l.startswith('mxtpu_srv_lat_bucket')]
    assert hb and all('model="clf"' in l and 'replica="1"' in l
                      for l in hb)
    base, labels = instrument.split_labeled_name(
        'a.b|model=m,replica=2')
    assert base == 'a.b' and labels == {'model': 'm', 'replica': '2'}
    assert instrument.split_labeled_name('plain') == ('plain', None)
