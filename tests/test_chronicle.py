"""Tier-1 tests for the chronicle plane (ISSUE 20): the continuous
telemetry journal (sample shapes, counter deltas across rotation, the
ring bound, torn-tail tolerance), the query API's window math, the
shared online detectors (no-flap on noise, level fire+clear, leak
slope), the anomaly -> decision -> postmortem path, the unified
decision-event API and timeline renderer, the off-by-default
zero-surface contract, render_prometheus timestamps, and check_trace's
decision-lane validation."""
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from mxnet_tpu import chronicle, detector, instrument

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, 'tools'))
import timeline  # noqa: E402
import check_trace  # noqa: E402

TIMELINE = os.path.join(REPO, 'tools', 'timeline.py')


@pytest.fixture(autouse=True)
def _clean_instrument_state():
    """Metrics + decision state are process-global: isolate and
    restore around every test so suite order never matters."""
    met = instrument.metrics_enabled()
    instrument.reset_metrics()
    saved = (list(instrument._decisions),
             dict(instrument._decision_seq),
             dict(instrument._decision_last_t),
             list(instrument._decision_sinks))
    instrument._decisions[:] = []
    instrument._decision_seq.clear()
    instrument._decision_last_t.clear()
    instrument._decision_sinks[:] = []
    instrument.set_metrics(True)
    yield
    chronicle.stop()
    (instrument._decisions[:], seq, last,
     instrument._decision_sinks[:]) = saved[0], saved[1], saved[2], \
        saved[3]
    instrument._decision_seq.clear()
    instrument._decision_seq.update(seq)
    instrument._decision_last_t.clear()
    instrument._decision_last_t.update(last)
    instrument.set_metrics(met)
    instrument.reset_metrics()


def _mk(tmp_path, **kw):
    kw.setdefault('every_ms', 100)
    kw.setdefault('detectors', {})
    return chronicle.Chronicle(str(tmp_path / 'journal'), **kw)


def _journal_records(jdir):
    recs = []
    for name in sorted(os.listdir(jdir)):
        if not name.startswith('journal-'):
            continue
        with open(os.path.join(jdir, name)) as f:
            for line in f:
                if line.strip():
                    recs.append(json.loads(line))
    return recs


# ---------------------------------------------------------------------------
# Journal: sample shapes, deltas, rotation, ring bound, torn tail
# ---------------------------------------------------------------------------

def test_sample_shapes_counters_gauges_hists(tmp_path):
    c = _mk(tmp_path)
    instrument.inc('work.items', 5)
    instrument.set_gauge('work.depth', 3.5)
    instrument.observe_hist('work.secs', 0.1)
    instrument.observe_hist('work.secs', 0.3)
    rec = c.sample(now=100.0)
    assert rec['kind'] == 'sample' and rec['t'] == 100.0
    total, delta, rate = rec['counters']['work.items']
    assert (total, delta, rate) == (5, 5, 0.0)  # first sample: no dt
    assert rec['gauges']['work.depth'] == 3.5
    h = rec['hists']['work.secs']
    assert h['count'] == 2 and h['sum'] == pytest.approx(0.4)
    assert h['buckets'] and h['buckets'][-1][1] == 2  # cumulative
    # the journal line is the same record
    on_disk = _journal_records(c.dir)
    assert on_disk[-1]['counters']['work.items'] == [5, 5, 0.0]
    c.close()


def test_counter_delta_and_rate_across_samples(tmp_path):
    c = _mk(tmp_path)
    instrument.inc('steps', 10)
    c.sample(now=100.0)
    instrument.inc('steps', 30)
    rec = c.sample(now=102.0)
    total, delta, rate = rec['counters']['steps']
    assert total == 40 and delta == 30
    assert rate == pytest.approx(15.0)
    c.close()


def test_rotation_and_ring_bound(tmp_path):
    # tiny ring: seg floor is 1 KiB, ring floor 2 KiB -> rotations and
    # oldest-segment drops both happen within a few hundred samples
    c = _mk(tmp_path, max_mb=2048 / (1024.0 * 1024.0))
    instrument.set_gauge('g', 1.0)
    for i in range(400):
        c.sample(now=1000.0 + i)
    segs = [n for n in os.listdir(c.dir)
            if n.startswith('journal-') and n != chronicle.ACTIVE_NAME]
    assert segs, 'no rotation happened'
    total = sum(os.path.getsize(os.path.join(c.dir, n))
                for n in os.listdir(c.dir) if n.startswith('journal-'))
    assert total <= c.max_bytes + c.seg_bytes  # bounded, not an archive
    snap = instrument.metrics_snapshot()['counters']
    assert snap.get('chronicle.rotations', 0) >= 1
    assert snap.get('chronicle.segments_dropped', 0) >= 1
    # counter continuity across rotation: deltas are all 1-ish per tick
    recs = [r for r in _journal_records(c.dir) if r['kind'] == 'sample']
    deltas = [r['counters']['chronicle.samples'][1] for r in recs[1:]]
    assert all(d == 1 for d in deltas)
    c.close()


def test_torn_tail_survives_readers(tmp_path):
    c = _mk(tmp_path)
    instrument.set_gauge('g', 2.0)
    for i in range(5):
        c.sample(now=200.0 + i)
    c.close()
    active = os.path.join(c.dir, chronicle.ACTIVE_NAME)
    with open(active, 'a') as f:
        f.write('{"kind": "sample", "t": 205.0, "ga')  # kill -9 tear
    # timeline tolerates the torn ACTIVE tail under --strict
    out = subprocess.run([sys.executable, TIMELINE, c.dir, '--strict'],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stdout + out.stderr
    # and a fresh Chronicle's disk-window read skips the torn line
    c2 = chronicle.Chronicle(c.dir, every_ms=100, detectors={})
    got = c2._window_samples(100.0, now=206.0)
    assert len(got) == 5
    c2.close()


# ---------------------------------------------------------------------------
# query(): gauges, counters, histograms, window math
# ---------------------------------------------------------------------------

def test_query_gauge_window_math(tmp_path):
    c = _mk(tmp_path)
    for i in range(10):
        instrument.set_gauge('speed', 10.0 + i)   # exactly linear
        c.sample(now=1000.0 + i)
    q = c.query('speed', 5.5, now=1009.0)  # samples t=1004..1009
    assert q['kind'] == 'gauge' and q['n'] == 6
    assert q['min'] == 14.0 and q['max'] == 19.0 and q['last'] == 19.0
    assert q['mean'] == pytest.approx(16.5)
    assert q['slope'] == pytest.approx(1.0)  # 1 unit per second
    assert c.query('no.such.series', 10.0, now=1009.0) == {}
    c.close()


def test_query_counter_rates_and_delta(tmp_path):
    c = _mk(tmp_path)
    for i in range(5):
        instrument.inc('reqs', 20)
        c.sample(now=500.0 + 2 * i)
    q = c.query('reqs', 100.0, now=508.0)
    assert q['kind'] == 'counter'
    assert q['total'] == 100 and q['delta'] == 100
    assert q['last'] == pytest.approx(10.0)   # 20 per 2s
    c.close()


def test_query_histogram_windowed_distribution(tmp_path):
    c = _mk(tmp_path)
    instrument.observe_hist('lat|lane=a', 0.001)
    c.sample(now=700.0)
    for _ in range(50):
        instrument.observe_hist('lat|lane=a', 0.010)
        instrument.observe_hist('lat|lane=b', 0.020)
    c.sample(now=701.0)
    q = c.query('lat', 10.0, now=701.0)
    assert q['kind'] == 'histogram' and q['n'] == 2
    assert q['count'] == 100          # window excludes the first obs
    assert q['p99'] is not None and q['p99'] > 0.005
    c.close()


def test_query_reads_closed_segments_when_memory_is_short(tmp_path):
    c = _mk(tmp_path, max_mb=8)   # large ring: nothing dropped
    instrument.set_gauge('g', 1.0)
    for i in range(50):
        c.sample(now=3000.0 + i)
    # amnesia: pretend memory only holds the last 5 samples
    while len(c._samples) > 5:
        c._samples.popleft()
    # force everything before memory onto disk as a closed segment
    with c._wlock:
        c._rotate_locked()
    q = c.query('g', 49.5, now=3049.0)
    assert q['n'] == 50               # disk filled the gap
    c.close()


# ---------------------------------------------------------------------------
# Detectors: no-flap, fire+clear, leak slope
# ---------------------------------------------------------------------------

def test_detector_quiet_on_noise():
    det = detector.SeriesDetector('s', direction='low')
    vals = [100.0, 101.0, 99.5, 100.2, 99.8] * 20
    assert all(det.observe(float(i), v) is None
               for i, v in enumerate(vals))


def test_detector_fires_on_sag_and_clears():
    det = detector.SeriesDetector('s', direction='low')
    t = [0.0]

    def feed(v):
        t[0] += 1.0
        return det.observe(t[0], v)

    for _ in range(20):
        assert feed(100.0) is None
    verdicts = [feed(40.0) for _ in range(4)]
    fired = [v for v in verdicts if v is not None]
    assert len(fired) == 1 and fired[0][0] == 'anomaly'
    info = fired[0][1]
    assert info['series'] == 's' and info['value'] == 40.0
    assert info['magnitude'] < -4.0 and len(info['window']) >= 2
    # recovery: enough in-band samples close and re-arm it
    cleared = [feed(100.0) for _ in range(10)]
    assert any(v is not None and v[0] == 'cleared' for v in cleared)
    assert det.active is False


def test_leak_detector_slope_mode():
    flat = detector.SeriesDetector('m', direction='slope')
    assert all(flat.observe(float(i), 1e9 + (i % 3)) is None
               for i in range(80))
    leak = detector.SeriesDetector('m', direction='slope')
    out = [leak.observe(float(i), 1e9 * (1.0 + 0.02 * i))
           for i in range(80)]
    fired = [v for v in out if v is not None]
    assert fired and fired[0][0] == 'anomaly'
    assert fired[0][1]['direction'] == 'slope'


def test_default_leak_detector_ignores_startup_ramp():
    """The stock mem.live_bytes detector must NOT page on training
    startup's allocation ramp (fast growth that then goes flat)."""
    det = chronicle.default_detectors()['mem.live_bytes']
    vals = [min(1.0, i / 10.0) * 4e9 for i in range(120)]  # ramp, flat
    assert all(det.observe(float(i), v) is None
               for i, v in enumerate(vals))


# ---------------------------------------------------------------------------
# Anomaly -> decision -> postmortem
# ---------------------------------------------------------------------------

def test_anomaly_emits_decision_and_postmortem(tmp_path):
    det = {'perf.steps_per_sec':
           detector.SeriesDetector('perf.steps_per_sec',
                                   direction='low')}
    c = _mk(tmp_path, detectors=det)
    for i in range(20):
        instrument.set_gauge('perf.steps_per_sec', 100.0)
        c.sample(now=100.0 + i)
    for i in range(4):
        instrument.set_gauge('perf.steps_per_sec', 20.0)
        c.sample(now=120.0 + i)
    evs = instrument.recent_decisions(subsystem='chronicle')
    anoms = [e for e in evs if e['action'] == 'anomaly']
    assert len(anoms) == 1            # hysteresis: one event, no flood
    ev = anoms[0]
    assert ev['series'] == 'perf.steps_per_sec'
    assert ev['severity'] == 'warn' and ev['value'] == 20.0
    snap = instrument.metrics_snapshot()['counters']
    assert snap.get('chronicle.anomalies') == 1
    pms = [n for n in os.listdir(c.dir)
           if n.startswith('flightrec-') and
           n.endswith('-anomaly.json')]
    assert len(pms) == 1
    with open(os.path.join(c.dir, pms[0])) as f:
        doc = json.load(f)
    anom = doc['anomaly']
    assert anom['series'] == 'perf.steps_per_sec'
    # the window embeds the breach that fired (2nd sag sample, t=121)
    assert [121.0, 20.0] in anom['window']
    # recovery emits anomaly_cleared
    for i in range(8):
        instrument.set_gauge('perf.steps_per_sec', 100.0)
        c.sample(now=130.0 + i)
    evs = instrument.recent_decisions(subsystem='chronicle')
    assert any(e['action'] == 'anomaly_cleared' for e in evs)
    c.close()


# ---------------------------------------------------------------------------
# Decision events: typed payloads, lanes, sinks, the journal recorder
# ---------------------------------------------------------------------------

def test_decision_event_typed_fields_and_lane_order():
    e1 = instrument.decision('testsub', 'scale_up', reason='p99 over',
                             model='m', replicas=3)
    e2 = instrument.decision('testsub', 'scale_down')
    other = instrument.decision('othersub', 'act')
    assert (e1['seq'], e2['seq']) == (1, 2)   # per-subsystem lanes
    assert other['seq'] == 1
    assert e2['t'] >= e1['t']                 # clamped non-decreasing
    assert e1['replicas'] == 3 and e1['severity'] == 'info'
    evs = instrument.recent_decisions(subsystem='testsub')
    assert [e['action'] for e in evs] == ['scale_up', 'scale_down']
    snap = instrument.metrics_snapshot()['counters']
    assert snap['decision.events'] == 3
    assert snap['decision.testsub'] == 2


def test_decision_ring_is_bounded_and_sinks_fed():
    seen = []
    instrument.on_decision(seen.append)
    instrument.on_decision(seen.append)       # idempotent
    for i in range(instrument.DECISION_RING + 50):
        instrument.decision('ringsub', 'tick', i=i)
    assert len(instrument._decisions) == instrument.DECISION_RING
    assert len(seen) == instrument.DECISION_RING + 50
    instrument.remove_decision_sink(seen.append)
    instrument.decision('ringsub', 'after')
    assert seen[-1]['action'] == 'tick'       # sink detached


def test_chronicle_records_decisions_in_journal(tmp_path):
    c = _mk(tmp_path)
    instrument.on_decision(c.record_decision)
    try:
        instrument.decision('faults', 'arm', reason='chaos on',
                            severity='warn')
    finally:
        instrument.remove_decision_sink(c.record_decision)
    c.close()
    recs = [r for r in _journal_records(c.dir)
            if r['kind'] == 'decision']
    assert len(recs) == 1
    assert recs[0]['ev']['subsystem'] == 'faults'
    assert recs[0]['ev']['action'] == 'arm'


# ---------------------------------------------------------------------------
# tools/timeline.py
# ---------------------------------------------------------------------------

def _write_journal(path, events):
    with open(path, 'w') as f:
        for ev in events:
            f.write(json.dumps({'kind': 'decision', 't': ev['t'],
                                'ev': ev}) + '\n')


def _ev(t, sub, action, seq, **kw):
    d = {'t': t, 'subsystem': sub, 'action': action, 'seq': seq,
         'reason': kw.pop('reason', ''), 'severity': 'info'}
    d.update(kw)
    return d


def test_timeline_merges_orders_and_windows(tmp_path, capsys):
    jdir = tmp_path / 'j'
    jdir.mkdir()
    _write_journal(str(jdir / 'journal-active.jsonl'), [
        _ev(100.0, 'faults', 'arm', 1),
        _ev(105.0, 'chronicle', 'anomaly', 1, reason='sps out of band'),
        _ev(300.0, 'elastic', 'shrink', 1),
    ])
    rc = timeline.main([str(jdir), '--strict'])
    out = capsys.readouterr().out
    assert rc == 0
    lines = [ln for ln in out.splitlines() if '[' in ln]
    assert len(lines) == 3
    assert 'faults.arm' in lines[0]
    assert 'chronicle.anomaly' in lines[1]   # time-ordered
    rc = timeline.main([str(jdir), '--around', '101', '--window', '5'])
    out = capsys.readouterr().out
    assert rc == 0
    assert 'faults.arm' in out and 'elastic.shrink' not in out


def test_timeline_strict_rejects_corrupt_and_disordered(tmp_path,
                                                        capsys):
    jdir = tmp_path / 'j'
    jdir.mkdir()
    # corrupt NON-tail line in a closed segment
    with open(str(jdir / 'journal-000001.jsonl'), 'w') as f:
        f.write('{"kind": "decision", "t": 1.0, "ev": {"t": 1.0, '
                '"subsystem": "a", "action": "x", "seq": 1}}\n')
        f.write('NOT JSON\n')
        f.write('{"kind": "decision", "t": 2.0, "ev": {"t": 2.0, '
                '"subsystem": "a", "action": "y", "seq": 2}}\n')
    assert timeline.main([str(jdir), '--strict']) == 2
    capsys.readouterr()
    # a lane whose seq and t order disagree
    jdir2 = tmp_path / 'j2'
    jdir2.mkdir()
    _write_journal(str(jdir2 / 'journal-active.jsonl'), [
        _ev(50.0, 'sub', 'later', 2),
        _ev(60.0, 'sub', 'earlier', 1),   # seq 1 AFTER seq 2 in time
    ])
    assert timeline.main([str(jdir2), '--strict']) == 2
    capsys.readouterr()
    # but duplicate seqs (two runs in one dir) are skipped, not errors
    _write_journal(str(jdir2 / 'journal-active.jsonl'), [
        _ev(50.0, 'sub', 'run1', 1),
        _ev(60.0, 'sub', 'run2', 1),
    ])
    assert timeline.main([str(jdir2), '--strict']) == 0
    capsys.readouterr()


def test_timeline_reads_flightrec_postmortems(tmp_path, capsys):
    pm = tmp_path / 'flightrec-rank0-x-anomaly.json'
    pm.write_text(json.dumps({
        'reason': 'x-anomaly', 'rank': '0', 'wall_time': 123.0,
        'anomaly': {'reason': 'x out of band'},
        'decisions': [_ev(120.0, 'health', 'abort', 1)],
    }))
    rc = timeline.main([str(pm), '--strict'])
    out = capsys.readouterr().out
    assert rc == 0
    assert 'health.abort' in out and 'flightrec:x-anomaly' in out
    assert out.index('health.abort') < out.index('flightrec')


# ---------------------------------------------------------------------------
# Off-by-default: zero surface, cheap off path
# ---------------------------------------------------------------------------

def test_off_by_default_zero_surface(monkeypatch):
    monkeypatch.delenv('MXTPU_CHRONICLE', raising=False)
    chronicle.stop()
    chronicle.refresh()
    assert not chronicle.enabled()
    assert chronicle.active() is None
    assert chronicle.query('perf.steps_per_sec', 10.0) == {}
    assert not any(t.name == chronicle.THREAD_NAME
                   for t in threading.enumerate())
    assert chronicle.start(dirpath='') is None


_FLOOR_ON = False


def _floor_query(a=None, b=None):
    if not _FLOOR_ON:
        return {}


def test_off_path_overhead_guard():
    """With the plane off, query() must stay single-check cheap:
    < 2x a same-shape inlined ideal floor."""
    chronicle.stop()
    n = 20000

    def measure(fn):
        best = float('inf')
        for _ in range(7):
            t0 = time.perf_counter()
            for _ in range(n):
                fn()
            best = min(best, time.perf_counter() - t0)
        return best

    real = measure(lambda: chronicle.query('perf.steps_per_sec', 5.0))
    floor = measure(lambda: _floor_query('perf.steps_per_sec', 5.0))
    assert real < 2.0 * max(floor, 1e-9), \
        'off-path query %.1fx the ideal floor' % (real / floor)


def test_start_implies_metrics_and_stop_detaches(tmp_path):
    instrument.set_metrics(False)
    c = chronicle.start(dirpath=str(tmp_path / 'j'), every_ms=50)
    try:
        assert c is not None and chronicle.enabled()
        assert instrument.metrics_enabled()   # the plane's input
        assert chronicle.start(dirpath='elsewhere') is c  # idempotent
        assert c.record_decision in instrument._decision_sinks
    finally:
        chronicle.stop()
    assert not chronicle.enabled()
    assert c.record_decision not in instrument._decision_sinks
    assert not any(t.name == chronicle.THREAD_NAME
                   for t in threading.enumerate())


# ---------------------------------------------------------------------------
# Satellites: prometheus timestamps, check_trace decision lanes
# ---------------------------------------------------------------------------

def test_render_prometheus_timestamps():
    instrument.inc('app.reqs', 3)
    instrument.observe_hist('app.lat', 0.12)
    plain = instrument.render_prometheus()
    again = instrument.render_prometheus(timestamp_ms=None)
    assert plain == again                      # default: byte-identical
    stamped = instrument.render_prometheus(timestamp_ms=1234567890123)
    for line in stamped.splitlines():
        if line.startswith('#') or not line.strip():
            continue                           # TYPE/HELP unstamped
        assert line.endswith(' 1234567890123'), line
    live = instrument.render_prometheus(timestamp_ms=True)
    sample = [ln for ln in live.splitlines()
              if ln.startswith('mxtpu_app_reqs_total')][0]
    assert abs(int(sample.split()[-1]) - time.time() * 1000) < 60000


def test_check_trace_validates_decision_lanes():
    def ev(name, ts, sub, seq):
        return {'name': name, 'ph': 'X', 'cat': 'decision', 'ts': ts,
                'dur': 0, 'pid': 1, 'tid': 1,
                'args': {'subsystem': sub, 'action': 'a', 'seq': seq}}

    good = [ev('decision.s.a', 100, 's', 1),
            ev('decision.s.a', 200, 's', 2)]
    assert not check_trace._validate_decision_events(good)
    bad_order = [ev('decision.s.a', 200, 's', 1),
                 ev('decision.s.a', 100, 's', 2)]
    errs = check_trace._validate_decision_events(bad_order)
    assert errs and 'disagree' in errs[0]
    untyped = [{'name': 'decision.s.a', 'ph': 'X', 'cat': 'decision',
                'ts': 1, 'dur': 0, 'pid': 1, 'tid': 1,
                'args': {'subsystem': 's'}}]
    errs = check_trace._validate_decision_events(untyped)
    assert errs and 'typed' in errs[0].lower()
    # two runs in one trace (duplicate seq) -> skipped, not an error
    two_runs = [ev('decision.s.a', 200, 's', 1),
                ev('decision.s.a', 100, 's', 1)]
    assert not check_trace._validate_decision_events(two_runs)


# ---------------------------------------------------------------------------
# Acceptance: the hermetic chronicle smoke (slow tier)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_check_chronicle_smoke():
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO, 'tools', 'check_chronicle.py')],
        capture_output=True, text=True, timeout=900,
        env={k: v for k, v in os.environ.items()
             if not k.startswith('MXTPU_')})
    assert out.returncode == 0, out.stdout + out.stderr
    assert 'chronicle smoke OK' in out.stdout
