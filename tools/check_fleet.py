#!/usr/bin/env python
"""Serving-fleet smoke: tp-sharded inference, replica scaling, the
closed-loop autoscaler and priority lanes — the docs/serving.md fleet
contract end to end (ISSUE 15).

The parent stays JAX-FREE and spawns one worker subprocess that pins
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` +
``JAX_PLATFORMS=cpu`` before jax initializes (hermetic on any box, like
tools/check_multichip.py), in which

1. **tp=2 oracle parity**: an MLP with INTEGER-valued parameters is
   served through a ``Predictor(mesh='dp=1,tp=2')`` behind a
   ``ModelServer`` and checked BUCKET-AWARE BIT-IDENTICAL against the
   single-chip oracle (per PR-6's contract: a response must bit-match
   the oracle padded to the same pow2 bucket).  Integer params/payloads
   make every pre-softmax value exactly representable, so any
   partial-sum regrouping the SPMD partitioner introduces is exact —
   the check pins PROGRAM equivalence; float payloads are additionally
   checked to 1e-6 (rounding-order noise is the only divergence).
   Warm sharded serving is asserted to take ZERO hot-path traces
   (``executor.xla_traces`` frozen while ``serving.sharded_aot_calls``
   moves), and the 'auto' partition's per-tensor degradation reasons
   are asserted present in the sharding-inspector records.
2. **2-replica qps scaling**: a fleet over a simulated accelerator
   (fixed per-flush service time behind a GIL-RELEASED wait — the
   latency shape of a real chip execute, measurable even on a 1-core
   CI host) must push closed-loop qps at the p99 SLO to >= 1.6x the
   1-replica figure at 2 replicas.  The same sweep also runs on a REAL
   compute model over disjoint virtual devices: on a multi-core host
   it must hit 1.6x too; on a single-core host (this box: compute
   cannot physically parallelize) it must at least not regress, and
   the tool says which bound it enforced.
3. **autoscaler on a load step**: traffic steps from idle to a
   saturating closed loop; the controller must detect the windowed-p99
   breach, scale 1->2 replicas, and the post-convergence p99 must be
   back under the SLO — with EVERY decision logged as an event
   (required fields asserted, event count == the
   ``serving.autoscale.decisions`` counter).
4. **priority lanes**: under a saturating batch-lane flood, the
   interactive lane's p99 must stay bounded (preemption at flush
   boundaries — ``serving.preempt_flushes`` > 0) while the batch
   lane's p99 collapses; per-lane labeled histograms must be present
   in the registry and the Prometheus exposition.
5. **chaos: supervised self-healing** (ISSUE 17): with the replica
   supervisor watching a 2-replica fleet, one replica's worker is
   KILLED (``serve.worker.r0:after:1:kill`` → ``InjectedDeath``) and
   the other's flush WEDGED for 30s (``serve.flush.r1:after:1:wedge``)
   mid-traffic; every client request must still resolve — served, or
   failed TYPED (deadline/quarantine/overload) — with ZERO lost or
   hung futures, both replicas quarantined + replaced (capacity back
   to 2, ``serving.quarantines`` >= 2, the wedged batch replayed at
   its lane head, ``serving.replica_recovery_secs`` gauge present) and
   the post-recovery p99 back under an absolute bound.  A
   deterministic brownout sub-phase then drives the autoscaler ladder
   by hand: sustained breach AT capacity must climb level 1 (batch
   lane shed, interactive still admitted) → 2 (max_batch halved) → 3
   (smallest bucket), and a sustained clear must de-escalate in
   reverse until the batch lane reopens.
6. **request attribution** (ISSUE 16): with MXTPU_SERVEWATCH on and a
   60ms fault injected on ONE replica's execute
   (``serve.execute.r1:delay``), slow requests must commit durable
   flight-record postmortems naming THAT replica with ``execute`` as
   the dominant bucket, buckets summing to e2e; the Prometheus
   exposition must carry request-id exemplars; the trace dump must
   pass ``check_trace``'s request-ledger validation; a
   ``merge_traces`` pass must render one ``serve <model>/r<N>`` lane
   per replica; and ``explain_request --strict`` must accept the
   postmortem.

Run from the repo root::

    python tools/check_fleet.py

Exit code 0 on success — the CI guard for the serving fleet.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Worker-side model builders
# ---------------------------------------------------------------------------

def int_mlp(d_in=32, hidden=64, classes=8, batch=8, seed=0):
    """(symbol_json, params, shapes, partition) of an MLP whose params
    are small integers: fp32 arithmetic on integers is EXACT, so every
    partial-sum regrouping a tp=2 partitioning introduces reproduces
    the single-chip bits (softmax then runs on bit-identical logits)."""
    import mxnet_tpu as mx
    from mxnet_tpu import sym
    net = sym.Variable('data')
    net = sym.FullyConnected(net, num_hidden=hidden, name='sfc1')
    net = sym.Activation(net, act_type='relu', name='sact1')
    net = sym.FullyConnected(net, num_hidden=classes, name='sfc2')
    net = sym.SoftmaxOutput(net, name='softmax')
    rng = np.random.RandomState(seed)
    arg_shapes, _, _ = net.infer_shape(data=(batch, d_in))
    params = {n: mx.nd.array(rng.randint(-2, 3, s).astype(np.float32))
              for n, s in zip(net.list_arguments(), arg_shapes)
              if n not in ('data', 'softmax_label')}
    # column-parallel first layer, row-parallel second, everything else
    # replicated: the classic Megatron split, all-exact on integers
    partition = {'sfc1': 'auto', 'sfc2_weight': (None, 'tp'),
                 'sfc2_bias': 'replicated', '': 'replicated'}
    return net.tojson(), params, {'data': (batch, d_in)}, partition


def real_model(d_in=256, hidden=512, classes=16, batch=8, seed=1):
    import mxnet_tpu as mx
    from mxnet_tpu import sym
    net = sym.Variable('data')
    net = sym.FullyConnected(net, num_hidden=hidden, name='hfc1')
    net = sym.Activation(net, act_type='relu', name='hact1')
    net = sym.FullyConnected(net, num_hidden=hidden, name='hfc2')
    net = sym.Activation(net, act_type='relu', name='hact2')
    net = sym.FullyConnected(net, num_hidden=classes, name='hfc3')
    net = sym.SoftmaxOutput(net, name='softmax')
    rng = np.random.RandomState(seed)
    ash, _, _ = net.infer_shape(data=(batch, d_in))
    params = {n: mx.nd.array((rng.randn(*s) * 0.1).astype(np.float32))
              for n, s in zip(net.list_arguments(), ash)
              if n not in ('data', 'softmax_label')}
    return net.tojson(), params, {'data': (batch, d_in)}


class SimChipPredictor(object):
    """A Predictor-shaped simulated accelerator: each forward costs a
    FIXED service time spent in a GIL-released wait (``time.sleep`` —
    exactly the latency shape of a real chip executing while the host
    thread blocks).  The fleet's concurrency mechanics (shared queue,
    per-replica workers, preemption, autoscaling) are measurable
    against it on ANY host, including the 1-core CI box where real
    compute cannot physically parallelize."""

    def __init__(self, shapes, classes=4, service_s=0.008):
        self._input_shapes = dict(shapes)
        self._batch_inputs = {'data'}
        self.num_outputs = 1
        self.service_s = float(service_s)
        self._out = None

    def forward(self, **kw):
        rows = kw['data'].shape[0]
        # the executable-signature hook real Predictors expose: the
        # serving execute wrapper reads it into flush records
        self._active_bucket = rows
        time.sleep(self.service_s)
        self._out = np.zeros((rows, 4), np.float32)

    def get_output(self, i):
        return self._out


# ---------------------------------------------------------------------------
# Leg 1: tp=2 sharded serving, bucket-aware bit-identical, zero traces
# ---------------------------------------------------------------------------

def leg_tp_parity():
    import jax

    from mxnet_tpu import instrument
    from mxnet_tpu.predictor import Predictor
    from mxnet_tpu.serving import ModelServer
    sym_json, params, shapes, partition = int_mlp()
    d_in = shapes['data'][1]

    oracle = Predictor(sym_json, params, dict(shapes), pad_to_bucket=True)
    sp = Predictor(sym_json, params, dict(shapes), mesh='dp=1,tp=2',
                   partition=partition, devices=jax.devices()[:2])
    recs = sp.sharding_records()
    sharded = [n for n, r in recs['params'].items() if any(r['spec'])]
    assert len(sharded) >= 3, \
        'expected tp-sharded params, records: %r' % recs['params']
    for f in sp.warm_buckets(8):
        f.result(timeout=300)

    server = ModelServer(max_delay_ms=3.0, max_batch=8)
    server.load_model('tp', predictor=sp, input_shapes=shapes)

    rng = np.random.RandomState(3)
    payloads = [rng.randint(0, 4, (1 + i % 5, d_in)).astype(np.float32)
                for i in range(48)]
    # oracle outputs per possible bucket, computed BEFORE freezing the
    # trace counter (the oracle's own bucket compiles are not serving
    # traces)
    oracle_by_bucket = []
    for x in payloads:
        outs = {}
        for b in (1, 2, 4, 8):
            if b < x.shape[0]:
                continue
            padded = np.concatenate(
                [x, np.zeros((b - x.shape[0], d_in), np.float32)])
            oracle.forward(data=padded)
            outs[b] = oracle.get_output(0)[:x.shape[0]].copy()
        oracle_by_bucket.append(outs)

    c0 = instrument.metrics_snapshot()['counters']
    tr0 = c0.get('executor.xla_traces', 0)
    aot0 = c0.get('serving.sharded_aot_calls', 0)
    mismatches = []
    lock = threading.Lock()

    def client(idxs):
        for i in idxs:
            got = server.predict('tp', data=payloads[i])[0]
            if not any(np.array_equal(got, w)
                       for w in oracle_by_bucket[i].values()):
                with lock:
                    mismatches.append(i)

    threads = [threading.Thread(target=client,
                                args=(range(k, len(payloads), 6),))
               for k in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not mismatches, \
        'tp=2 responses diverged from the single-chip oracle at ' \
        'payloads %s' % mismatches[:8]
    c1 = instrument.metrics_snapshot()['counters']
    traces = c1.get('executor.xla_traces', 0) - tr0
    aot = c1.get('serving.sharded_aot_calls', 0) - aot0
    assert traces == 0, \
        'warm sharded serving took %d hot-path traces' % traces
    assert aot >= len(payloads) // 4, \
        'sharded AOT executables barely ran (%d calls)' % aot

    # float payloads: bit-identity is an integer-arithmetic property;
    # floats pin the same program to rounding-order noise only
    x = rng.rand(3, d_in).astype(np.float32)
    got = server.predict('tp', data=x)[0]
    padded = np.concatenate([x, np.zeros((1, d_in), np.float32)])
    oracle.forward(data=padded)
    want = oracle.get_output(0)[:3]
    assert np.allclose(got, want, rtol=1e-6, atol=1e-7)

    # 'auto' on a tp-indivisible tensor must surface a REASON through
    # the sharding inspector, not silently replicate
    from mxnet_tpu import sym
    import mxnet_tpu as mx
    odd = sym.SoftmaxOutput(sym.FullyConnected(
        sym.Variable('data'), num_hidden=63, name='ofc'), name='softmax')
    ash, _, _ = odd.infer_shape(data=(4, 31))
    op = {n: mx.nd.array(rng.randint(-1, 2, s).astype(np.float32))
          for n, s in zip(odd.list_arguments(), ash)
          if n not in ('data', 'softmax_label')}
    sp2 = Predictor(odd.tojson(), op, {'data': (4, 31)}, mesh='1x2',
                    partition='auto', devices=jax.devices()[:2])
    reasons = [(n, r['reason'])
               for n, r in sp2.sharding_records()['params'].items()
               if r.get('reason')]
    assert reasons and 'no tp-divisible dim' in reasons[0][1], \
        'degradation reasons missing from inspector records: %r' % reasons
    server.close(drain=False)
    log('check_fleet: tp=2 parity OK (%d payloads bit-identical, '
        '%d AOT calls, 0 hot traces, %d degradation reasons)'
        % (len(payloads), aot, len(reasons)))


# ---------------------------------------------------------------------------
# Leg 2: replica qps scaling
# ---------------------------------------------------------------------------

def _sweep(server, name, make_inputs, slo_ms, duration_s=1.2,
           max_concurrency=16):
    sys.path.insert(0, os.path.join(ROOT, 'tools'))
    import serve_bench
    best, sweep = serve_bench.find_qps_at_slo(
        server, name, make_inputs, slo_p99_ms=slo_ms,
        duration_s=duration_s, max_concurrency=max_concurrency)
    return best or {'qps': 0.0, 'p99_ms': float('inf')}, sweep


def leg_fleet_scaling():
    from mxnet_tpu.serving import ModelServer

    # -- mechanics: simulated accelerator, deterministic on any host --
    # service time chosen so the simulated chip, not single-core host
    # Python, is the bottleneck: 25ms/flush x max_batch 4 caps one
    # replica at ~160 rps — far under the ~1.4k rps the host's request
    # plumbing sustains, so doubling replicas can genuinely double qps
    shapes = {'data': (8, 16)}
    sim = [SimChipPredictor(shapes, service_s=0.025) for _ in range(2)]
    server = ModelServer(max_delay_ms=1.0, max_batch=4, max_queue=512)
    server.load_model('sim', predictor=sim[0], input_shapes=shapes)
    # scale_up builds replicas through the server's builder: hand it
    # the spare simulated chip for slot 1
    orig_build = server._build_predictor

    def build(slot=0, **kw):
        return sim[slot] if slot < len(sim) else orig_build(slot=slot,
                                                            **kw)
    server._build_predictor = build
    x = np.zeros((1, 16), np.float32)

    def mk():
        return {'data': x}

    slo_ms = 200.0
    s1, _ = _sweep(server, 'sim', mk, slo_ms)
    assert server.scale_up('sim') == 2
    s2, _ = _sweep(server, 'sim', mk, slo_ms)
    scaling_sim = s2['qps'] / max(s1['qps'], 1e-9)
    if scaling_sim < 1.6:
        # one retry (the check_io pattern): a transient host stall
        # inside either sweep skews the ratio on this 1-core box
        log('check_fleet: sim scaling %.2fx noisy — host stall? '
            'retrying both sweeps once' % scaling_sim)
        assert server.scale_down('sim') == 1
        s1, _ = _sweep(server, 'sim', mk, slo_ms)
        assert server.scale_up('sim') == 2
        s2, _ = _sweep(server, 'sim', mk, slo_ms)
        scaling_sim = s2['qps'] / max(s1['qps'], 1e-9)
    log('check_fleet: sim fleet 1r %.0f qps (p99 %.1fms) -> 2r %.0f '
        'qps (p99 %.1fms): %.2fx'
        % (s1['qps'], s1['p99_ms'], s2['qps'], s2['p99_ms'],
           scaling_sim))
    assert scaling_sim >= 1.6, \
        'fleet mechanics failed to scale: %.2fx < 1.6x (the shared ' \
        'queue is not feeding both replica workers)' % scaling_sim
    server.close(drain=False)

    # -- real model over disjoint virtual devices --------------------
    sym_json, params, shapes = real_model()
    server = ModelServer(max_delay_ms=1.0, max_batch=8)
    server.load_model('real', symbol_json=sym_json, params=params,
                      input_shapes=shapes)
    rng = np.random.RandomState(0)
    xr = rng.rand(4, shapes['data'][1]).astype(np.float32)

    def mkr():
        return {'data': xr}

    server.predict('real', data=xr)          # compile out of the path
    slo_ms = 250.0
    r1, _ = _sweep(server, 'real', mkr, slo_ms)
    assert server.scale_up('real') == 2
    r2, _ = _sweep(server, 'real', mkr, slo_ms)
    scaling_real = r2['qps'] / max(r1['qps'], 1e-9)
    cores = os.cpu_count() or 1
    if cores >= 2:
        floor, why = 1.6, '%d-core host: full scaling bound' % cores
    else:
        # one core: two compute-bound replicas cannot physically beat
        # one — the fleet must at least add no overhead
        floor, why = 0.85, 'single-core host: no-regression bound ' \
            '(compute cannot parallelize; the 1.6x contract is ' \
            'enforced on the simulated-accelerator fleet above)'
    if scaling_real < floor:
        log('check_fleet: real scaling %.2fx noisy — host stall? '
            'retrying both sweeps once' % scaling_real)
        assert server.scale_down('real') == 1
        r1, _ = _sweep(server, 'real', mkr, slo_ms)
        assert server.scale_up('real') == 2
        r2, _ = _sweep(server, 'real', mkr, slo_ms)
        scaling_real = r2['qps'] / max(r1['qps'], 1e-9)
    log('check_fleet: real fleet 1r %.0f qps -> 2r %.0f qps: %.2fx '
        '(%s)' % (r1['qps'], r2['qps'], scaling_real, why))
    assert scaling_real >= floor, \
        'real-model fleet scaling %.2fx under the %.2fx bound (%s)' \
        % (scaling_real, floor, why)
    server.close(drain=False)


# ---------------------------------------------------------------------------
# Leg 3: autoscaler on an injected load step
# ---------------------------------------------------------------------------

def leg_autoscale():
    from mxnet_tpu import instrument
    from mxnet_tpu.serving import ModelServer
    sys.path.insert(0, os.path.join(ROOT, 'tools'))
    import serve_bench

    # 20ms/flush x max_batch 4 puts the 1-replica level (~8 clients /
    # 200 rps = ~40ms) and the 2-replica level (~20ms) far enough
    # apart that an SLO at 70% of the measured 1-replica p99 has real
    # margin on BOTH sides of the scale-up, even under 1-core jitter
    shapes = {'data': (8, 16)}
    sims = [SimChipPredictor(shapes, service_s=0.020) for _ in range(3)]
    server = ModelServer(max_delay_ms=1.0, max_batch=4, max_queue=512)
    server.load_model('as', predictor=sims[0], input_shapes=shapes)
    # spare replicas for scale_up: stash prebuilts the server can adopt
    spare = {1: sims[1], 2: sims[2]}
    orig_build = server._build_predictor

    def build(slot=0, **kw):
        return spare.get(slot) or orig_build(slot=slot, **kw)
    server._build_predictor = build
    x = np.zeros((1, 16), np.float32)

    def mk():
        return {'data': x}

    # calibrate: saturating 8-client load on ONE replica
    cal = serve_bench.closed_loop(server, 'as', mk, duration_s=1.5,
                                  concurrency=8)
    slo_ms = 0.70 * cal['p99_ms']
    log('check_fleet: autoscale calibration p99 %.1fms at 1 replica '
        '-> SLO %.1fms' % (cal['p99_ms'], slo_ms))
    dec0 = int(instrument.counter_value('serving.autoscale.decisions'))
    # min_batch == max_batch: the simulated chip's service time is
    # per-flush, so batch shrinking cannot buy latency here — pin it
    # off and let replica scaling be the only actuator under test
    sc = server.autoscale('as', slo_p99_ms=slo_ms, interval_s=0.25,
                          max_replicas=2, up_after=2, down_after=50,
                          min_batch=4, min_samples=8, cooldown_s=1.0)

    # the load STEP: idle -> saturating closed loop held for 8s
    res = {}

    def load():
        res['step'] = serve_bench.closed_loop(server, 'as', mk,
                                              duration_s=8.0,
                                              concurrency=8)
    t = threading.Thread(target=load)
    t.start()
    t.join()
    actions = [e['action'] for e in sc.events]
    assert 'scale_up' in actions, \
        'autoscaler never scaled on the load step: %r' % sc.events
    assert server.replica_count('as') == 2
    # post-convergence: the SAME load must now meet the SLO.  Up to
    # THREE windows with a settle pause between (the check_io
    # escalation pattern): an external process hammering this 1-core
    # box can fatten two consecutive 2s windows — the control OUTCOME
    # (2 replicas, decisions logged) is already asserted above, so the
    # retries only de-noise the latency-recovery measurement.
    post = None
    for attempt in range(3):
        post = serve_bench.closed_loop(server, 'as', mk,
                                       duration_s=2.0, concurrency=8)
        if post['p99_ms'] <= slo_ms:
            break
        log('check_fleet: post-convergence window %d over SLO '
            '(%.1fms) — host stall? settling and retrying'
            % (attempt + 1, post['p99_ms']))
        time.sleep(1.0)
    log('check_fleet: autoscale converged — p99 %.1fms vs SLO %.1fms '
        'at 2 replicas (%d decisions: %s)'
        % (post['p99_ms'], slo_ms, len(sc.events), actions))
    assert post['p99_ms'] <= slo_ms, \
        'p99 %.1fms still over the %.1fms SLO after scale-up' \
        % (post['p99_ms'], slo_ms)
    # every decision is a fully-formed logged event, and the counter
    # agrees with the log
    for ev in sc.events:
        for k in ('t', 'model', 'action', 'reason', 'slo_p99_ms',
                  'replicas', 'max_batch'):
            assert k in ev, 'decision event missing %r: %r' % (k, ev)
    dec = int(instrument.counter_value('serving.autoscale.decisions'))
    assert dec - dec0 == len(sc.events), \
        'decision counter (%d) != event log (%d)' % (dec - dec0,
                                                     len(sc.events))
    server.close(drain=False)


# ---------------------------------------------------------------------------
# Leg 4: priority lanes under a saturating batch flood
# ---------------------------------------------------------------------------

def leg_priority():
    from mxnet_tpu import instrument
    from mxnet_tpu.serving import ModelServer
    shapes = {'data': (8, 16)}
    server = ModelServer(max_delay_ms=1.0, max_batch=4, max_queue=512)
    server.load_model('pr', predictor=SimChipPredictor(
        shapes, service_s=0.008), input_shapes=shapes)
    x = np.zeros((1, 16), np.float32)
    sys.path.insert(0, os.path.join(ROOT, 'tools'))
    import serve_bench

    def measure():
        stop = threading.Event()
        batch_lat = []
        lock = threading.Lock()

        def flood():
            local = []
            while not stop.is_set():
                t0 = time.monotonic()
                try:
                    server.predict('pr', data=x)
                except Exception:
                    continue
                local.append(time.monotonic() - t0)
            with lock:
                batch_lat.extend(local)

        floods = [threading.Thread(target=flood) for _ in range(12)]
        for t in floods:
            t.start()
        time.sleep(0.5)                   # flood reaches steady state
        inter_lat = []
        for _ in range(40):
            t0 = time.monotonic()
            server.predict('pr', priority='interactive', data=x)
            inter_lat.append(time.monotonic() - t0)
            time.sleep(0.02)
        stop.set()
        for t in floods:
            t.join()
        return (1e3 * serve_bench.percentile(inter_lat, 0.99),
                1e3 * serve_bench.percentile(batch_lat, 0.99))

    p99_i, p99_b = measure()
    if not (p99_i < 0.6 * p99_b and p99_i < 60.0):
        # one retry (the check_io pattern): a transient host stall on
        # this 1-core box inflates BOTH lanes and squeezes the ratio
        log('check_fleet: priority window noisy (interactive %.1fms / '
            'batch %.1fms) — host stall? retrying once'
            % (p99_i, p99_b))
        p99_i, p99_b = measure()
    snap = instrument.metrics_snapshot()
    preempts = snap['counters'].get('serving.preempt_flushes', 0)
    log('check_fleet: priority lanes — interactive p99 %.1fms vs '
        'batch p99 %.1fms under flood (%d preempt flushes)'
        % (p99_i, p99_b, preempts))
    assert preempts > 0, 'interactive never preempted batch coalescing'
    assert p99_i < 0.6 * p99_b, \
        'interactive p99 %.1fms not held under batch flood ' \
        '(batch p99 %.1fms)' % (p99_i, p99_b)
    assert p99_i < 60.0, \
        'interactive p99 %.1fms above the absolute bound (service ' \
        'time 8ms: preemption should hold it near 2 flushes)' % p99_i
    hists = snap.get('histograms') or {}
    lane_series = [k for k in hists if 'lane=interactive' in k]
    assert lane_series, 'no interactive-lane labeled histograms'
    prom = instrument.render_prometheus()
    assert 'lane="interactive"' in prom, \
        'per-lane labels missing from the Prometheus exposition'
    assert 'replica="0"' in prom, \
        'per-replica labels missing from the Prometheus exposition'
    server.close(drain=False)


# ---------------------------------------------------------------------------
# Leg 5: chaos — supervised self-healing under kill + wedge, brownout
# ---------------------------------------------------------------------------

def leg_chaos():
    """The self-healing contract end to end (docs/serving.md "Failure
    semantics"): a supervised 2-replica fleet takes a worker KILL and a
    30s flush WEDGE mid-traffic and must lose NOTHING — every request
    resolves (served or typed), both corpses are quarantined and
    replaced, and the p99 recovers."""
    from mxnet_tpu import instrument, resilience
    from mxnet_tpu.serving import (DeadlineExceededError, ModelServer,
                                   ReplicaQuarantinedError,
                                   ServerOverloadedError)
    sys.path.insert(0, os.path.join(ROOT, 'tools'))
    import serve_bench

    shapes = {'data': (8, 16)}
    # spares for EVERY slot: quarantine frees device slots for reuse,
    # so a replacement can land on ANY slot including 0
    spare = {i: SimChipPredictor(shapes, service_s=0.008)
             for i in range(8)}
    server = ModelServer(max_delay_ms=1.0, max_batch=4, max_queue=512)
    server.load_model('cx', predictor=spare[0], input_shapes=shapes)
    orig_build = server._build_predictor

    def build(slot=0, **kw):
        return spare.get(slot) or orig_build(slot=slot, **kw)
    server._build_predictor = build
    assert server.scale_up('cx') == 2
    sup = server.supervise('cx', wedge_ms=300, interval_s=0.05)
    x = np.zeros((1, 16), np.float32)
    for _ in range(8):                     # both replicas, fault-free
        server.predict('cx', data=x)

    # the chaos plan: replica 0's worker dies on its next loop pass
    # (InjectedDeath — the process survives); replica 1's next flush
    # wedges for 30s holding its in-flight batch.  Both directives
    # fire ONCE, so replacements reusing the freed slots are healthy.
    q0 = int(instrument.counter_value('serving.quarantines'))
    resilience.set_faults('serve.worker.r0:after:1:kill;'
                          'serve.flush.r1:after:1:wedge:30')
    lost, lat = [], []
    lock = threading.Lock()
    stop = threading.Event()

    def client():
        ok, bad = [], []
        while not stop.is_set():
            t0 = time.monotonic()
            try:
                server.predict('cx', data=x, deadline_ms=2000.0,
                               timeout=10.0)
                ok.append(time.monotonic() - t0)
            except (DeadlineExceededError, ReplicaQuarantinedError,
                    ServerOverloadedError):
                pass               # typed and bounded — resolved, not lost
            except Exception as e:  # noqa: BLE001 - the leg's verdict
                bad.append(repr(e))
        with lock:
            lat.extend(ok)
            lost.extend(bad)

    threads = [threading.Thread(target=client) for _ in range(6)]
    for t in threads:
        t.start()
    try:
        # hold traffic until the supervisor has quarantined BOTH
        # replicas and restored capacity (bounded: the wedge detects at
        # 300ms, the kill on the next tick; repairs are sub-second on
        # the simulated chip)
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            healed = int(instrument.counter_value(
                'serving.quarantines')) - q0 >= 2 \
                and server.replica_count('cx') == 2
            if healed:
                break
            time.sleep(0.1)
        time.sleep(0.5)            # post-repair traffic on the spares
    finally:
        stop.set()
        for t in threads:
            t.join()
        resilience.clear_faults()

    assert not lost, \
        'chaos lost %d request(s) (hung or untyped): %s' \
        % (len(lost), lost[:4])
    quarantines = int(instrument.counter_value(
        'serving.quarantines')) - q0
    assert quarantines >= 2, \
        'supervisor quarantined %d of the 2 broken replicas' \
        % quarantines
    assert server.replica_count('cx') == 2, \
        'capacity not restored: %d replicas' % server.replica_count('cx')
    replays = int(instrument.counter_value('serving.replays'))
    assert replays >= 1, \
        'the wedged flush was seized but nothing was replayed'
    actions = [e['action'] for e in sup.events]
    assert 'quarantine' in actions and 'replace' in actions, \
        'supervision events incomplete: %r' % actions
    recoveries = [e['recovery_s'] for e in sup.events
                  if e['action'] == 'replace']
    gauges = instrument.metrics_snapshot().get('gauges') or {}
    assert 'serving.replica_recovery_secs|model=cx' in gauges, \
        'replica_recovery_secs gauge missing: %r' % sorted(gauges)
    assert len(lat) >= 20, \
        'chaos window served only %d requests — traffic never ' \
        'reached the repaired fleet' % len(lat)

    # post-recovery: the repaired fleet must serve at the healthy
    # shape.  One retry (the check_io pattern) de-noises a host stall.
    post = serve_bench.closed_loop(server, 'cx', lambda: {'data': x},
                                   duration_s=1.5, concurrency=6)
    if post['p99_ms'] > 250.0:
        log('check_fleet: post-chaos p99 %.1fms noisy — host stall? '
            'retrying once' % post['p99_ms'])
        post = serve_bench.closed_loop(server, 'cx',
                                       lambda: {'data': x},
                                       duration_s=1.5, concurrency=6)
    assert post['p99_ms'] <= 250.0, \
        'post-recovery p99 %.1fms never recovered (8ms service, ' \
        '2 repaired replicas)' % post['p99_ms']
    log('check_fleet: chaos OK — %d quarantines, %d replays, %d '
        'requests served, 0 lost, recovery %.3fs, post-recovery '
        'p99 %.1fms'
        % (quarantines, replays, len(lat), max(recoveries),
           post['p99_ms']))
    server.close(drain=False)

    # -- deterministic brownout ladder --------------------------------
    # a 1-replica fleet AT capacity under sustained breach must degrade
    # in the documented order — and climb back down on clear
    server = ModelServer(max_delay_ms=1.0, max_batch=4, max_queue=512)
    sim = SimChipPredictor(shapes, service_s=0.02)
    server.load_model('bx', predictor=sim, input_shapes=shapes)
    sc = server.autoscale('bx', slo_p99_ms=5.0, interval_s=0,
                          up_after=1, down_after=1, min_samples=3,
                          cooldown_s=0, max_replicas=1, min_batch=2,
                          brownout=True, start=False)
    sc.async_actuation = False
    batcher = server._entry('bx').batcher

    def breach_tick(lane=None):
        for _ in range(4):
            server.predict('bx', priority=lane, data=x)
        return sc.tick()

    levels = []
    for _ in range(3):
        evs = breach_tick(lane=None if not batcher.shed_batch
                          else 'interactive')
        levels.extend(e.get('level') for e in evs
                      if e['action'] == 'brownout')
    assert levels == [1, 2, 3], \
        'brownout ladder climbed %r, want [1, 2, 3]' % levels
    assert batcher.shed_batch and batcher.max_batch == 2
    # level >= 1: the batch lane sheds, interactive is still admitted
    try:
        server.predict('bx', data=x)
        raise AssertionError('browned-out batch lane still admitted')
    except ServerOverloadedError:
        pass
    server.predict('bx', priority='interactive', data=x)
    gauges = instrument.metrics_snapshot().get('gauges') or {}
    assert gauges.get('serving.brownout_level|model=bx') == 3
    # clear: fast service well under the SLO de-escalates in reverse
    sim.service_s = 0.0
    sc._watches['bx'].slo_p99_ms = 1000.0
    down = []
    for _ in range(2):
        evs = breach_tick(lane='interactive')
        down.extend((e['action'], e.get('level')) for e in evs)
    assert down and down[0][0] == 'restore_batch', \
        'de-escalation did not restore buckets first: %r' % down
    assert ('brownout', 0) in down, \
        'the batch lane never reopened: %r' % down
    assert not batcher.shed_batch and batcher.max_batch == 4
    server.predict('bx', data=x)           # batch lane admits again
    gauges = instrument.metrics_snapshot().get('gauges') or {}
    assert gauges.get('serving.brownout_level|model=bx') == 0
    log('check_fleet: brownout ladder OK — up %r, down %r'
        % (levels, [a for a, _ in down]))
    server.close(drain=False)


# ---------------------------------------------------------------------------
# Leg 6: request attribution — traced fleet, injected slow replica
# ---------------------------------------------------------------------------

def leg_request_attribution():
    """The hermetic proof of the request-attribution plane: one
    replica of a 2-replica fleet gets a 60ms execute stall injected
    (``resilience`` fault plan), and the plane must name it — durable
    postmortems carrying replica 1 and ``execute`` as the dominant
    bucket, exemplar request ids in the exposition, a ledger-valid
    trace, per-replica merged lanes, and an ``explain_request``
    waterfall that accepts the postmortem.  Runs LAST: installing the
    flight recorder turns span tracing on for the rest of the
    process."""
    import atexit
    import shutil
    from mxnet_tpu import health, instrument, resilience
    from mxnet_tpu.serving import ModelServer, servewatch
    sys.path.insert(0, os.path.join(ROOT, 'tools'))
    import check_trace
    import explain_request
    import merge_traces

    tmpdir = tempfile.mkdtemp(prefix='mxtpu_fleet_trace_')
    # registered BEFORE the recorder installs its atexit dump, so LIFO
    # ordering removes the dir only after the final 'exit' dump lands
    atexit.register(shutil.rmtree, tmpdir, ignore_errors=True)
    shapes = {'data': (8, 16)}
    sims = [SimChipPredictor(shapes, service_s=0.004) for _ in range(2)]
    server = ModelServer(max_delay_ms=1.0, max_batch=4, max_queue=512)
    try:
        health.install_flight_recorder(tmpdir)
        servewatch.set_enabled(True)
        servewatch.set_slow_ms(30.0)
        server.load_model('pm', predictor=sims[0], input_shapes=shapes)
        orig_build = server._build_predictor

        def build(slot=0, **kw):
            return sims[slot] if slot < len(sims) else \
                orig_build(slot=slot, **kw)
        server._build_predictor = build
        assert server.scale_up('pm') == 2
        x = np.zeros((1, 16), np.float32)
        for _ in range(8):                 # both replicas, fault-free
            server.predict('pm', data=x)
        # a 60ms stall on replica 1's execute ONLY (2x the 30ms slow
        # threshold; replica 0's 4ms service stays far under it)
        resilience.set_faults('serve.execute.r1:delay:1.0:0.06')
        try:
            futs = [server.submit('pm', data=x) for _ in range(24)]
            for f in futs:
                f.result(timeout=30)
        finally:
            resilience.clear_faults()

        slow = [p for p in servewatch.postmortems()
                if p['kind'] == 'slow']
        assert slow, 'injected replica stall committed no postmortem'
        assert all(str(p['replica']) == '1' for p in slow), \
            'postmortems blame the wrong replica: %r' % slow
        # the MAJORITY must pin execute as dominant: on a 1-core box
        # the delivery loop can occasionally be preempted past the
        # 60ms stall, legitimately tipping one request's ledger to
        # slice_deliver — the plane measured a real stall either way
        culprit = [p for p in slow if p['dominant'] == 'execute']
        assert len(culprit) * 2 >= len(slow) and culprit, \
            'dominant bucket should be execute for most slow ' \
            'requests: %r' % slow

        # the durable file IS the forensic record: reload it cold and
        # check the ledger + flush composition survived serialization
        pm = culprit[-1]
        assert pm['path'] and os.path.exists(pm['path'])
        with open(pm['path']) as f:
            doc = json.load(f)
        payload = doc[doc['reason']]
        assert payload['req_id'] == pm['req_id']
        total = sum(payload['buckets_ms'][b] for b in
                    ('admission_wait', 'lane_wait', 'coalesce_wait',
                     'pad', 'execute', 'slice_deliver'))
        assert abs(total - payload['e2e_ms']) <= \
            max(1e-3, 0.01 * payload['e2e_ms']), \
            'postmortem buckets (%.3fms) do not sum to e2e (%.3fms)' \
            % (total, payload['e2e_ms'])
        assert payload['buckets_ms']['execute'] >= 50.0, \
            'the 60ms injected stall is missing from the execute ' \
            'bucket: %r' % payload['buckets_ms']
        fl = payload['flush']
        assert pm['req_id'] in fl['req_ids'] and \
            'SimChipPredictor' in (fl['sig'] or ''), \
            'flush composition incomplete: %r' % fl
        assert payload['admission']['queue_depth'] >= 0

        prom = instrument.render_prometheus()
        assert '# {request_id="' in prom, \
            'request-id exemplars missing from the exposition'

        trace = os.path.join(tmpdir, 'fleet_rank0.json')
        instrument.dump_trace(trace)
        errors = check_trace.validate_file(trace)
        assert not errors, \
            'request-span ledger validation failed: %s' % errors[:5]

        merged = merge_traces.merge([trace])
        names = {e['args']['name'] for e in merged['traceEvents']
                 if e.get('ph') == 'M' and e.get('name') == 'thread_name'}
        assert {'serve pm/r0', 'serve pm/r1'} <= names, \
            'merged dump lacks per-replica lanes: %r' % sorted(names)

        rc = explain_request.main([pm['path'], '--strict'])
        assert rc == 0, 'explain_request --strict rejected the ' \
            'postmortem (rc %d)' % rc
        log('check_fleet: request attribution OK (%d postmortems '
            'naming replica 1, %d execute-dominant, exemplars + '
            'ledger-valid trace + %d replica lanes)'
            % (len(slow), len(culprit), 2))
    finally:
        servewatch.set_slow_ms(0.0)
        servewatch.set_enabled(False)
        server.close(drain=False)


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def worker():
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import mxnet_tpu  # noqa: F401 - full package wiring
    from mxnet_tpu import instrument
    assert instrument.metrics_enabled(), 'worker needs MXTPU_METRICS=1'
    assert len(jax.devices()) >= 4, \
        'worker needs the 8-virtual-device XLA_FLAGS pin'

    leg_tp_parity()
    leg_fleet_scaling()
    leg_autoscale()
    leg_priority()
    leg_chaos()
    leg_request_attribution()
    log('check_fleet worker OK')


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--worker', action='store_true', help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.worker:
        worker()
        return 0

    env = dict(os.environ)
    env.update({'MXTPU_METRICS': '1', 'JAX_PLATFORMS': 'cpu',
                'XLA_FLAGS': '--xla_force_host_platform_device_count=8'})
    for k in ('MXTPU_MESH', 'MXTPU_PARTITION', 'MXTPU_PROFILE'):
        env.pop(k, None)
    cmd = [sys.executable, os.path.abspath(__file__), '--worker']
    out = subprocess.run(cmd, env=env, timeout=900,
                         capture_output=True, text=True)
    sys.stderr.write(out.stderr)
    if out.returncode != 0:
        print('check_fleet worker FAILED (rc %d)' % out.returncode,
              file=sys.stderr)
        sys.stderr.write(out.stdout[-2000:])
        return 1
    print('check_fleet OK', file=sys.stderr)
    return 0


if __name__ == '__main__':
    sys.exit(main())
