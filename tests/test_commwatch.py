"""Tier-1 tests for the communication-attribution plane (ISSUE 9):
HLO collective parsing + the analytic wire-byte model, per-executable
accounting on a live sharded fit, the comm-vs-compute roofline split,
the sharding inspector (degradation records, warn-once, counter,
explain_sharding rendering, mesh-free shapes mode), cross-rank step
skew (compute_step_skew units + the health plane's laggard threshold),
merged-trace clock alignment (merge_traces anchor shift + check_trace
offset-inconsistency rejection), and the knobs-off overhead guard."""
import json
import logging
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import commwatch, health, instrument, perfwatch
from mxnet_tpu.kvstore_server import compute_step_skew
from mxnet_tpu.parallel import mesh as pmesh
from mxnet_tpu.parallel.zero import zero_spec_for

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, 'tools'))
import check_trace  # noqa: E402
import explain_sharding  # noqa: E402
import merge_traces  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_comm_state():
    """commwatch/perfwatch state is process-global: restore everything
    so the rest of the suite (overhead floors, knobs-off guards) is
    unaffected."""
    prof = instrument.profiling_enabled()
    met = instrument.metrics_enabled()
    instrument.reset_metrics()
    commwatch.set_enabled(False)
    commwatch.clear_programs()
    perfwatch.set_enabled(False)
    perfwatch.clear_executables()
    yield
    commwatch.refresh()
    commwatch.set_enabled(False)
    commwatch.clear_programs()
    perfwatch.set_enabled(False)
    perfwatch.clear_executables()
    instrument.set_profiling(prof)
    instrument.set_metrics(met)
    instrument.reset_metrics()


# ---------------------------------------------------------------------------
# Leg 1 units: HLO parsing + the wire-byte model
# ---------------------------------------------------------------------------

_HLO = '''
HloModule jit_step

ENTRY %main {
  %p0 = f32[256]{0} parameter(0)
  %ar = f32[256]{0} all-reduce(f32[256]{0} %p0), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add
  %mar = (f32[4]{0}, f32[8]{0}) all-reduce(f32[4]{0} %p3, f32[8]{0} %p4), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add
  %ags = (bf16[32,8]{1,0}, bf16[64,8]{1,0}) all-gather-start(bf16[32,8]{1,0} %p1), replica_groups=[4,2]<=[8], dimensions={0}
  %agd = bf16[64,8]{1,0} all-gather-done((bf16[32,8]{1,0}, bf16[64,8]{1,0}) %ags)
  %rs = f32[32]{0} reduce-scatter(f32[256]{0} %ar), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %cp = u8[16]{0} collective-permute(u8[16]{0} %p2), source_target_pairs={{0,1}}
  %use = f32[256]{0} add(f32[256]{0} %ar, f32[256]{0} %ar)
}
'''


def test_parse_collectives():
    got = commwatch.parse_collectives(_HLO, num_devices=8)
    # async -done halves never double-count; operand REFERENCES
    # (the add consuming %ar) never match; a SYNC tuple LHS sums its
    # multi-operand members while an ASYNC -start tuple counts only
    # its (operand, result) result slot
    assert got == [
        ('all-reduce', 256 * 4, 4),        # brace groups of 4
        ('all-reduce', 4 * 4 + 8 * 4, 4),  # multi-operand sync tuple
        ('all-gather', 64 * 8 * 2, 2),     # iota [4,2] -> groups of 2
        ('reduce-scatter', 32 * 4, 8),
        ('collective-permute', 16, 8),
    ]
    stats = commwatch.collective_stats(_HLO, num_devices=8)
    assert stats['all-reduce']['count'] == 2
    assert stats['all-reduce']['bytes'] == 1024.0 + 48.0
    assert stats['all-reduce']['wire_bytes'] == \
        pytest.approx(2.0 * (1024 + 48) * 3 / 4)
    assert commwatch.collective_stats('no collectives here') == {}


def test_wire_bytes_model():
    # ring all-reduce: 2N(g-1)/g; degenerate group of 1 moves nothing
    assert commwatch.wire_bytes('all-reduce', 1000, 4) == \
        pytest.approx(1500.0)
    assert commwatch.wire_bytes('all-reduce', 1000, 1) == 0.0
    # all-gather result is the GATHERED tensor: N(g-1)/g
    assert commwatch.wire_bytes('all-gather', 1000, 4) == \
        pytest.approx(750.0)
    # reduce-scatter result is one SHARD: N(g-1)
    assert commwatch.wire_bytes('reduce-scatter', 250, 4) == \
        pytest.approx(750.0)
    assert commwatch.wire_bytes('collective-permute', 1000, 4) == 1000.0


def test_comm_fraction_bounds(monkeypatch):
    assert commwatch.comm_fraction(0.0, 1e9, peak_flops=1e12,
                                   peak_bw=1e9) == 0.0
    assert commwatch.comm_fraction(1e6, 0.0, peak_flops=1e12,
                                   peak_bw=1e9) == 1.0
    f = commwatch.comm_fraction(1e6, 1e9, peak_flops=1e12, peak_bw=1e9)
    assert f == pytest.approx(0.5)
    # MXTPU_PEAK_BW pins the interconnect denominator
    monkeypatch.setenv('MXTPU_PEAK_BW', '123.0')
    assert commwatch.interconnect_bw() == 123.0
    monkeypatch.delenv('MXTPU_PEAK_BW')
    assert commwatch.interconnect_bw('TPU v4 pod chip') == \
        commwatch.ICI_PEAKS['TPU v4']
    # a kind the table lacks (the CPU backend included) is an error
    with pytest.raises(mx.MXNetError, match='weird-accelerator'):
        commwatch.interconnect_bw('weird-accelerator')
    with pytest.raises(mx.MXNetError, match='MXTPU_PEAK_BW'):
        commwatch.interconnect_bw()


def test_analyze_executable_gauges():
    """A real sharded jit's compiled HLO feeds the comm.* gauges via
    analyze_executable (the perfwatch.register_executable hook)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    commwatch.set_enabled(True)
    devs = np.array(jax.devices()[:4])
    m = Mesh(devs, ('dp',))
    sh = NamedSharding(m, P('dp'))
    x = jax.device_put(jnp.ones((8, 16), jnp.float32), sh)
    compiled = jax.jit(lambda v: v.sum(),
                       in_shardings=sh,
                       out_shardings=NamedSharding(m, P())) \
        .lower(x).compile()
    row = commwatch.analyze_executable('t', 'sig0', compiled,
                                       num_devices=4)
    assert row is not None
    assert row['collectives'].get('all-reduce', {}).get('count', 0) >= 1
    assert row['wire_bytes_per_step'] > 0
    g = instrument.metrics_snapshot()['gauges']
    assert g['comm.all_reduce.count'] >= 1
    assert g['comm.all_reduce.bytes'] > 0
    assert g['comm.all_reduce.wire_bytes'] > 0
    assert g['comm.executables'] == 1
    # idempotent per (kind, key): re-analysis returns the cached row
    assert commwatch.analyze_executable('t', 'sig0', compiled,
                                        num_devices=4) is row \
        or commwatch.program_info('t', 'sig0') is not None
    assert g['comm.executables'] == 1


# ---------------------------------------------------------------------------
# Live fit: accounting + roofline split + step cadence (comm plane alone)
# ---------------------------------------------------------------------------

def _mlp():
    net = mx.sym.Variable('data')
    net = mx.sym.FullyConnected(net, num_hidden=32, name='fc1')
    net = mx.sym.Activation(net, act_type='relu', name='act1')
    net = mx.sym.FullyConnected(net, num_hidden=8, name='fc2')
    return mx.sym.SoftmaxOutput(net, name='softmax')


def _fit(mesh=None, partition=None, sym=None, rows=128, d=16, classes=8):
    """One fit with MXTPU_COMMWATCH exported for its duration — fit's
    activate_fit re-reads the env var, so a bare set_enabled would be
    clobbered at the first batch."""
    rng = np.random.RandomState(0)
    X = rng.randn(rows, d).astype(np.float32)
    Y = (rng.rand(rows) * classes).astype(np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=32)
    mx.random.seed(7)
    mod = mx.mod.Module(sym or _mlp(), context=mx.cpu())
    saved = os.environ.get('MXTPU_COMMWATCH')
    os.environ['MXTPU_COMMWATCH'] = '1'
    try:
        mod.fit(it, num_epoch=1, optimizer='sgd',
                optimizer_params={'learning_rate': 0.1,
                                  'momentum': 0.9},
                eval_metric='acc', initializer=mx.init.Uniform(0.05),
                mesh=mesh, partition=partition)
    finally:
        if saved is None:
            os.environ.pop('MXTPU_COMMWATCH', None)
        else:
            os.environ['MXTPU_COMMWATCH'] = saved
    return mod


def test_sharded_fit_collective_accounting():
    """commwatch ALONE (perfwatch off) accounts a sharded fit's
    collectives and publishes the roofline split + step cadence."""
    commwatch.set_enabled(True)
    assert not perfwatch.enabled()
    mod = _fit(mesh='4x2', partition='auto')
    assert mod._fused is not None
    snap = instrument.metrics_snapshot()
    g = snap['gauges']
    assert g.get('comm.all_reduce.count', 0) > 0
    assert g.get('comm.all_reduce.bytes', 0) > 0
    assert g.get('comm.all_gather.bytes', 0) > 0 or \
        g.get('comm.reduce_scatter.bytes', 0) > 0
    assert g.get('comm.bytes_per_step', 0) > 0
    assert 0.0 <= g['perf.comm_fraction'] <= 1.0
    # dispatch-to-dispatch cadence: 4 batches -> >= 2 intervals
    h = snap.get('histograms') or {}
    assert h.get('comm.step_time', {}).get('count', 0) >= 2
    # the exposition carries the split for scrapes
    assert 'mxtpu_perf_comm_fraction' in instrument.render_prometheus()


def test_analytic_allreduce_bytes_dp4():
    """Pure dp=4: the gradient all-reduce wire bytes must reproduce the
    analytic ring formula 2*(dp-1)/dp * param_bytes."""
    commwatch.set_enabled(True)
    mod = _fit(mesh='4x1', partition=None)
    param_bytes = sum(int(np.prod(v.shape)) * 4
                      for v in mod.get_params()[0].values())
    g = instrument.metrics_snapshot()['gauges']
    expect = 2.0 * 3 / 4 * param_bytes
    got = g.get('comm.all_reduce.wire_bytes', 0)
    # metric-delta scalar reduces ride along: small absolute slack
    assert abs(got - expect) <= 0.25 * expect + 256, (got, expect)


def test_single_device_zero_comm():
    commwatch.set_enabled(True)
    _fit(mesh='1x1')
    g = instrument.metrics_snapshot()['gauges']
    assert not any(v for k, v in g.items()
                   if k.startswith('comm.') and
                   k.endswith(('.bytes', '.wire_bytes', '_per_step')))


# ---------------------------------------------------------------------------
# Leg 2: sharding inspector
# ---------------------------------------------------------------------------

def test_degradation_recorded_and_warned(caplog):
    """'auto' with no tp-divisible dim degrades to replicated — the
    plan records the per-tensor reason, warns ONCE naming the params,
    and bumps mesh.degraded_params."""
    commwatch.set_enabled(True)
    net = mx.sym.Variable('data')
    net = mx.sym.FullyConnected(net, num_hidden=7, name='fc1')
    net = mx.sym.SoftmaxOutput(net, name='softmax')
    with caplog.at_level(logging.WARNING):
        mod = _fit(mesh='4x2', partition='auto', sym=net, d=15,
                   classes=7)
    plan = mod._mesh_plan
    bad = plan.degraded_params()
    assert {n for n, _ in bad} == {'fc1_weight', 'fc1_bias'}
    assert all('no tp-divisible dim' in r for _, r in bad)
    warns = [r for r in caplog.records if 'REPLICATED' in r.getMessage()]
    assert len(warns) == 1
    assert 'fc1_weight' in warns[0].getMessage()
    c = instrument.metrics_snapshot()['counters']
    assert c.get('mesh.degraded_params') == 2
    # warn-once per plan: a second note is a no-op
    plan.note_degraded()
    assert instrument.metrics_snapshot()['counters'][
        'mesh.degraded_params'] == 2
    # the records document renders through the inspector tool
    doc = plan.records_doc()
    assert doc['schema'] == 'mxtpu-sharding-plan-1'
    assert explain_sharding.render(doc, out=open(os.devnull, 'w')) == 2


def test_healthy_plan_records_no_degradation():
    commwatch.set_enabled(True)
    mod = _fit(mesh='4x2', partition='auto')
    plan = mod._mesh_plan
    assert plan.degraded_params() == []
    rec = plan.records['fc1_weight']
    assert rec['reason'] is None
    assert 'tp' in rec['spec']
    # tp=2 halves the fc1 weight shard
    full = int(np.prod(rec['shape'])) * 4
    assert rec['shard_bytes'] == full // 2
    # ZeRO leaves recorded with a dp split
    assert any('dp' in l['spec'] for l in rec['opt_leaves'])


def test_plan_records_idempotent_across_rebuilds():
    """A fused-step rebuild re-derives shardings on the SAME sticky
    plan: the inspector records must not duplicate opt leaves."""
    plan = pmesh.make_plan('4x2', partition='auto')
    for _ in range(3):
        plan.param_sharding('w', (32, 16), dtype=np.float32)
        plan.begin_opt_records(['w'])
        plan.opt_leaf_sharding('w', (32, 16), dtype=np.float32)
    assert len(plan.records['w']['opt_leaves']) == 1
    # a placement-time param_sharding call AFTER the derivation pass
    # (executor_group._place_data) must not erase the leaves
    plan.param_sharding('w', (32, 16), dtype=np.float32)
    assert len(plan.records['w']['opt_leaves']) == 1
    # ... nor may a dtype-LESS call rewrite a non-f32 record's shard
    # bytes with the 4-byte fallback
    plan.param_sharding('h', (8, 16), dtype=np.float16)
    b16 = plan.records['h']['shard_bytes']
    plan.param_sharding('h', (8, 16))
    assert plan.records['h']['shard_bytes'] == b16
    assert plan.records['h']['dtype'] == 'float16'


def test_records_for_shapes_matches_live_rules():
    """The mesh-free shapes mode (explain_sharding --mesh/--shape) uses
    the same selection rules as the live plan."""
    doc = pmesh.records_for_shapes(
        {'fc1_weight': (32, 16), 'odd': (15, 7)}, '4x2',
        partition='auto', opt_slots=2)
    w = doc['params']['fc1_weight']
    assert w['reason'] is None and 'tp' in w['spec']
    assert len(w['opt_leaves']) == 2
    odd = doc['params']['odd']
    assert odd['spec'] == () and 'no tp-divisible dim' in odd['reason']
    # zero_spec_for composes dp on top of the tp base
    assert zero_spec_for((32, 16), 4, base=('tp',)) == ('tp', 'dp')
    assert zero_spec_for((3, 5), 4, base=()) == ()
    # explain_sharding CLI shapes mode, --strict exit 2 on degradation
    rc = explain_sharding.main(['--mesh', '4x2', '--partition', 'auto',
                                '--shape', 'odd:15x7', '--strict'])
    assert rc == 2
    rc = explain_sharding.main(['--mesh', '4x2', '--partition', 'auto',
                                '--shape', 'w:32x16', '--strict'])
    assert rc == 0


# ---------------------------------------------------------------------------
# Leg 3: cross-rank skew
# ---------------------------------------------------------------------------

def test_compute_step_skew_units():
    # fewer than two usable histograms: no attribution
    assert compute_step_skew({}) == (0.0, None)
    assert compute_step_skew(
        {0: {'histograms': {'comm.step_time': {'count': 9, 'sum': 1.0}}}}
    ) == (0.0, None)
    ranks = {
        0: {'histograms': {'comm.step_time': {'count': 10, 'sum': 1.0}}},
        1: {'histograms': {'comm.step_time': {'count': 10, 'sum': 1.0}}},
        2: {'histograms': {'comm.step_time': {'count': 10, 'sum': 3.0}}},
        3: {'histograms': {'comm.step_time': {'count': 1, 'sum': 9.9}}},
        4: {'histograms': {'comm.step_time': {'count': 'x'}}},
    }
    skew, laggard = compute_step_skew(ranks)
    # rank 3 (count < 2) and rank 4 (garbage) are ignored; median of
    # [.1, .1, .3] = .1 -> rank 2 runs 200% over
    assert laggard['rank'] == 2
    assert skew == pytest.approx(2.0)
    assert laggard['pct_over_median'] == pytest.approx(200.0)
    assert set(laggard['means']) == {'0', '1', '2'}


def test_note_skew_threshold_and_throttle(monkeypatch):
    laggard = {'rank': 3, 'mean_step_secs': 0.2,
               'median_step_secs': 0.1, 'pct_over_median': 100.0}
    # knob off: never warns
    assert not health.note_skew(1.0, laggard)
    monkeypatch.setenv('MXTPU_SKEW_WARN_PCT', '50')
    health._skew_warned.clear()
    instrument.set_metrics(True)
    try:
        # under threshold: no warning
        assert not health.note_skew(0.3, laggard)
        assert health.note_skew(1.0, laggard, now=100.0)
        # throttled inside the per-rank window, re-arms after it
        assert not health.note_skew(1.0, laggard, now=101.0)
        assert health.note_skew(1.0, laggard,
                                now=101.0 + health._SKEW_WARN_INTERVAL)
        c = instrument.metrics_snapshot()['counters']
        assert c.get('health.skew_warnings') == 2
    finally:
        health._skew_warned.clear()


def test_barrier_wait_histogram():
    commwatch.set_enabled(True)
    commwatch.barrier_wait(0.01)
    commwatch.barrier_wait(0.02)
    snap = instrument.metrics_snapshot()
    assert snap['histograms']['comm.barrier_wait']['count'] == 2
    assert snap['counters']['comm.barriers'] == 2


# ---------------------------------------------------------------------------
# Satellite: merged-trace clock alignment
# ---------------------------------------------------------------------------

def _rank_trace(path, base_us, rank):
    """One rank's dump: a barrier span ending at base_us + 1000 and a
    work span after it."""
    events = [
        {'name': 'kvstore.barrier', 'ph': 'X', 'pid': 0, 'tid': 1,
         'ts': base_us, 'dur': 1000, 'cat': 'kvstore'},
        {'name': 'module.fused_step', 'ph': 'X', 'pid': 0, 'tid': 1,
         'ts': base_us + 2000, 'dur': 500, 'cat': 'executor'},
    ]
    with open(path, 'w') as f:
        json.dump({'traceEvents': events}, f)


def test_merge_traces_aligns_rank_clocks(tmp_path):
    """Rank clocks offset by seconds (different monotonic epochs) are
    aligned on the barrier anchor; the merged dump validates."""
    p0, p1 = str(tmp_path / 'rank0.json'), str(tmp_path / 'rank1.json')
    _rank_trace(p0, 1_000_000, 0)
    _rank_trace(p1, 900_000_000, 1)     # ~15 min of clock skew
    doc = merge_traces.merge([p0, p1])
    sync = {e['pid']: e['args'] for e in doc['traceEvents']
            if e.get('ph') == 'M' and e.get('name') == 'clock_sync'}
    assert sync[0]['aligned'] and sync[1]['aligned']
    assert sync[0]['anchor'] == 'kvstore.barrier'
    # both lanes' barrier ends coincide after the shift
    ends = {}
    for e in doc['traceEvents']:
        if e.get('name') == 'kvstore.barrier' and e.get('ph') == 'X':
            ends[e['pid']] = e['ts'] + e['dur']
    assert ends[0] == pytest.approx(ends[1])
    assert check_trace.validate_events(doc['traceEvents']) == []
    # --no-align keeps raw timestamps and emits no clock_sync claim
    raw = merge_traces.merge([p0, p1], align=False)
    assert not any(e.get('name') == 'clock_sync'
                   for e in raw['traceEvents'])


def test_check_trace_rejects_offset_inconsistent_lanes(tmp_path):
    """A merged dump CLAIMING alignment whose lanes disagree on the
    anchor instant past tolerance is rejected."""
    events = []
    for rank, end in ((0, 1000_000), (1, 2000_000)):   # 1s apart
        events.append({'name': 'clock_sync', 'ph': 'M', 'pid': rank,
                       'args': {'anchor': 'kvstore.barrier',
                                'offset_us': 0, 'aligned': True}})
        events.append({'name': 'kvstore.barrier', 'ph': 'X',
                       'pid': rank, 'tid': 1, 'ts': end - 1000,
                       'dur': 1000, 'cat': 'kvstore'})
    errors = check_trace.validate_events(events)
    assert errors and 'offset-inconsistent' in errors[0]
    # within tolerance: accepted
    for e in events:
        if e['pid'] == 1 and e.get('ph') == 'X':
            e['ts'] = 1000_000 + 100 - 1000     # 100us apart
    assert check_trace.validate_events(events) == []


def test_unanchored_lane_merges_unaligned(tmp_path):
    p0, p1 = str(tmp_path / 'rank0.json'), str(tmp_path / 'rank1.json')
    _rank_trace(p0, 1_000_000, 0)
    with open(p1, 'w') as f:
        json.dump({'traceEvents': [
            {'name': 'module.fused_step', 'ph': 'X', 'pid': 0, 'tid': 1,
             'ts': 5_000, 'dur': 500, 'cat': 'executor'}]}, f)
    doc = merge_traces.merge([p0, p1])
    # one anchor only -> no reference, nothing shifted, no false claim
    sync = [e for e in doc['traceEvents']
            if e.get('name') == 'clock_sync' and
            (e.get('args') or {}).get('aligned')]
    assert sync == []
    assert check_trace.validate_events(doc['traceEvents']) == []


# ---------------------------------------------------------------------------
# Off-path guard
# ---------------------------------------------------------------------------

_FLOOR_ON = False


def _floor_hook(a=None, b=None, c=None, d=None):
    """Same-shape inlined ideal: one module-global flag check."""
    if not _FLOOR_ON:
        return None


def test_knobs_off_overhead_guard():
    """With MXTPU_COMMWATCH off every hook is one module-global check:
    < 2x a same-shape inlined floor (the perfwatch/health pin)."""
    commwatch.set_enabled(False)
    assert not commwatch.enabled()
    n = 20000

    def measure(fn):
        best = float('inf')
        for _ in range(7):
            t0 = time.perf_counter()
            for _ in range(n):
                fn()
            best = min(best, time.perf_counter() - t0)
        return best

    pairs = (
        ('analyze_executable',
         lambda: commwatch.analyze_executable('k', 's', None),
         lambda: _floor_hook('k', 's', None)),
        ('on_step', lambda: commwatch.on_step('k', 's', 0.01, 1e9),
         lambda: _floor_hook('k', 's', 0.01, 1e9)),
        ('barrier_wait', lambda: commwatch.barrier_wait(0.01),
         lambda: _floor_hook(0.01)),
    )
    worst = []
    for name, hook, floor_fn in pairs:
        ratio = min((measure(hook) + 0.0) / max(measure(floor_fn), 1e-9)
                    for _ in range(3))
        worst.append((name, ratio))
    for name, ratio in worst:
        assert ratio < 2.0, \
            ('%s off-path is %.2fx its floor (all: %s)'
             % (name, ratio, worst))


# ---------------------------------------------------------------------------
# Acceptance: the hermetic communication-plane smoke
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_check_comm_e2e():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'check_comm.py')],
        capture_output=True, text=True, timeout=1200,
        env={k: v for k, v in os.environ.items()
             if not k.startswith('MXTPU_')})
    assert out.returncode == 0, out.stdout + out.stderr
    assert 'communication-plane smoke OK' in out.stdout
