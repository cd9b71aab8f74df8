#!/usr/bin/env python
"""Communication-plane smoke: collective accounting, sharding
inspector and cross-rank straggler attribution end to end — the
acceptance gate of the docs/observability.md "communication plane"
(hermetic: the parent never imports jax; children pin their own CPU
backend and virtual device counts).

Three legs:

1. **Collective accounting** (8 virtual devices, ``MXTPU_COMMWATCH``
   only — the comm plane must not depend on MXTPU_PERFWATCH): a
   ``mesh='4x2', partition='auto'`` fit reports nonzero all-reduce AND
   gather/scatter bytes, a positive ``comm.bytes_per_step``, a
   ``perf.comm_fraction`` in [0, 1] present in BOTH the metrics
   registry and the Prometheus exposition; a ``mesh='4x1', replicated``
   fit's gradient all-reduce wire bytes match the analytic ring
   formula ``(dp-1)/dp · 2 · param_bytes`` within tolerance.

2. **Sharding inspector**: a fit whose parameters have no
   tp-divisible dims degrades to replicated — the plan records the
   per-tensor reason, ``mesh.degraded_params`` bumps, and
   ``tools/explain_sharding.py`` renders the reason from the dumped
   records (``--strict`` exits 2).

3. **Straggler attribution** (2-worker ``dist_async``): rank 1 runs
   under ``MXTPU_FAULTS='fit.step:delay:1:0.08'`` — every step 80ms
   slower.  The per-rank ``comm.step_time`` histograms ride the
   heartbeat piggyback; the kv server's merged view must name rank 1
   (``cluster.step_skew`` gauge + attribution in
   ``cluster_status.json``/``.prom``), and with
   ``MXTPU_SKEW_WARN_PCT=20`` armed the health plane commits a
   ``skew`` flight record for the laggard.

Usage: ``python tools/check_comm.py [--keep]``.  Exits nonzero on any
failed assertion.  CPU-safe; run by ``tests/test_commwatch.py`` (slow
marker) and by hand after touching commwatch/kvstore telemetry.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)


# ---------------------------------------------------------------------------
# children
# ---------------------------------------------------------------------------

def _mlp(mx, hidden=32, classes=8):
    net = mx.sym.Variable('data')
    net = mx.sym.FullyConnected(net, num_hidden=hidden, name='fc1')
    net = mx.sym.Activation(net, act_type='relu', name='act1')
    net = mx.sym.FullyConnected(net, num_hidden=classes, name='fc2')
    return mx.sym.SoftmaxOutput(net, name='softmax')


def _child_fit(mode, outdir):
    """One fit; prints a JSON result line.  Modes: 'sharded' (4x2
    auto), 'analytic' (4x1 replicated), 'degraded' (4x2 auto, odd
    dims)."""
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import numpy as np
    sys.path.insert(0, _REPO)
    import mxnet_tpu as mx
    from mxnet_tpu import commwatch, instrument, perfwatch

    assert commwatch.enabled(), 'MXTPU_COMMWATCH did not arm'
    assert not perfwatch.enabled(), \
        'leg must run with perfwatch OFF (comm plane stands alone)'

    rng = np.random.RandomState(0)
    if mode == 'degraded':
        # every parameter dim odd -> nothing divides tp=2
        d, classes = 15, 7
        net = mx.sym.Variable('data')
        net = mx.sym.FullyConnected(net, num_hidden=classes, name='fc1')
        net = mx.sym.SoftmaxOutput(net, name='softmax')
        mesh, partition = '4x2', 'auto'
    else:
        d, classes = 16, 8
        net = _mlp(mx, hidden=32, classes=classes)
        mesh = '4x2' if mode == 'sharded' else '4x1'
        partition = 'auto' if mode == 'sharded' else None
    X = rng.randn(128, d).astype(np.float32)
    Y = (rng.rand(128) * classes).astype(np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=64)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=2, optimizer='sgd',
            optimizer_params={'learning_rate': 0.1, 'momentum': 0.9},
            eval_metric='acc', initializer=mx.init.Uniform(0.05),
            mesh=mesh, partition=partition)
    assert mod._fused is not None, 'fit did not take the fused path'

    snap = instrument.metrics_snapshot()
    out = {'mode': mode,
           'counters': snap['counters'],
           'gauges': {k: v for k, v in snap['gauges'].items()
                      if k.startswith(('perf.', 'comm.', 'mesh.'))
                      and '[' not in k},
           'param_bytes': int(sum(
               int(np.prod(v.shape)) * 4
               for v in mod.get_params()[0].values())),
           'prom_has_fraction':
               'mxtpu_perf_comm_fraction' in
               instrument.render_prometheus()}
    if mode == 'degraded':
        doc = mod._mesh_plan.records_doc()
        plan_path = os.path.join(outdir, 'plan.json')
        with open(plan_path, 'w') as f:
            json.dump(doc, f)
        out['plan'] = plan_path
        out['degraded'] = [n for n, r in sorted(doc['params'].items())
                          if r.get('reason')]
    print(json.dumps(out))


def _worker_skew(outdir):
    """One rank of the 2-worker straggler leg (rank from
    MXTPU_PROCESS_ID; rank 1 carries the fit.step delay fault)."""
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import numpy as np
    sys.path.insert(0, _REPO)
    import mxnet_tpu as mx
    from mxnet_tpu import commwatch, instrument

    assert commwatch.enabled()
    kv = mx.kv.create('dist_async')
    rank = kv.rank

    rng = np.random.RandomState(rank)
    bs, d, classes = 16, 10, 4
    X = rng.randn(8 * bs, d).astype(np.float32)
    Y = (X @ rng.randn(d, classes)).argmax(1).astype(np.float32)
    net = _mlp(mx, hidden=16, classes=classes)
    it = mx.io.NDArrayIter(X, Y, batch_size=bs, shuffle=False)
    mod = mx.mod.Module(net)
    mod.fit(it, num_epoch=1, optimizer='sgd', kvstore='local',
            optimizer_params={'learning_rate': 0.1},
            eval_metric='acc', initializer=mx.init.Uniform(0.05))
    h = instrument.metrics_snapshot().get('histograms', {})
    assert h.get('comm.step_time', {}).get('count', 0) >= 2, \
        'rank %d recorded no step cadence: %r' % (rank, sorted(h))

    # let the heartbeat piggyback deliver the histograms, then hold the
    # cluster together long enough for the server's merged view (and
    # its throttled status write) to see BOTH ranks' final state
    kv.barrier()
    time.sleep(3.2)
    if rank == 0:
        view = kv.telemetry()
        skew = view['cluster']['gauges'].get('cluster.step_skew', 0)
        laggard = view['cluster'].get('step_skew')
        assert laggard is not None, 'no straggler attribution: %r' \
            % (view['cluster'],)
        assert laggard['rank'] == 1, \
            'wrong laggard named: %r' % (laggard,)
        assert skew > 0.5, 'skew too small for an 80ms/step delay: %r' \
            % (skew,)
        print('check_comm: skew view OK (skew=%.2f, laggard=rank %s)'
              % (skew, laggard['rank']), flush=True)
    kv.barrier()
    kv.close()
    print('check_comm worker rank %d OK' % rank, flush=True)


# ---------------------------------------------------------------------------
# parent
# ---------------------------------------------------------------------------

def _nominal_cpu_peaks(env):
    """The peak tables hold real chips only: nominal figures keep
    perf.comm_fraction defined on the CPU backend."""
    env.setdefault('MXTPU_PEAK_FLOPS', '2e11')
    env.setdefault('MXTPU_PEAK_BW', '1e10')


def _run_fit_child(mode, outdir):
    env = dict(os.environ)
    flags = env.get('XLA_FLAGS', '')
    if 'xla_force_host_platform_device_count' not in flags:
        env['XLA_FLAGS'] = \
            flags + ' --xla_force_host_platform_device_count=8'
    env.update({'JAX_PLATFORMS': 'cpu', 'MXTPU_COMMWATCH': '1',
                'MXTPU_PERFWATCH': '0', 'MXTPU_WARM_START': '0'})
    _nominal_cpu_peaks(env)
    for k in ('MXTPU_MESH', 'MXTPU_PARTITION', 'MXTPU_COMPILE_CACHE',
              'MXTPU_FAULTS'):
        env.pop(k, None)
    out = subprocess.run([sys.executable, os.path.abspath(__file__),
                          '--run-child', mode, '--outdir', outdir],
                         env=env, capture_output=True, text=True,
                         timeout=900)
    if out.returncode != 0:
        print(out.stdout)
        print(out.stderr, file=sys.stderr)
        raise RuntimeError('%s child failed (rc %d)'
                           % (mode, out.returncode))
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stderr


def _run_skew_leg(outdir):
    port = 9930 + (os.getpid() * 7) % 40
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.pop('JAX_PLATFORMS', None)
        env.pop('MXTPU_MESH', None)
        env.pop('MXTPU_PARTITION', None)
        _nominal_cpu_peaks(env)
        env.update({
            'MXTPU_PROCESS_ID': str(rank),
            'MXTPU_NUM_PROCESSES': '2',
            'MXTPU_KV_SERVER_ADDR': '127.0.0.1:%d' % port,
            'MXTPU_METRICS': '1',
            'MXTPU_COMMWATCH': '1',
            'MXTPU_KV_BARRIER_TIMEOUT': '90',
        })
        if rank == 0:
            # the server rank holds the merged view: arm the status
            # files, the laggard threshold and the flight recorder
            env.update({'MXTPU_TELEMETRY_DIR': outdir,
                        'MXTPU_SKEW_WARN_PCT': '20',
                        'MXTPU_FLIGHT_RECORDER': outdir})
        else:
            # rank 1 IS the straggler: 80ms injected before every
            # fused step dispatch
            env['MXTPU_FAULTS'] = 'fit.step:delay:1:0.08'
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), '--skew-worker',
             '--outdir', outdir], env=env))
    rcs = [p.wait(timeout=600) for p in procs]
    assert rcs == [0, 0], 'skew workers failed: %r' % (rcs,)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--run-child', default=None, help=argparse.SUPPRESS)
    ap.add_argument('--skew-worker', action='store_true',
                    help=argparse.SUPPRESS)
    ap.add_argument('--outdir', default=None, help=argparse.SUPPRESS)
    ap.add_argument('--keep', action='store_true')
    args = ap.parse_args(argv)

    if args.run_child:
        _child_fit(args.run_child, args.outdir)
        return 0
    if args.skew_worker:
        _worker_skew(args.outdir)
        return 0

    outdir = tempfile.mkdtemp(prefix='mxtpu_comm_')
    failures = []

    def check(cond, msg):
        print('%s %s' % ('OK  ' if cond else 'FAIL', msg))
        if not cond:
            failures.append(msg)

    try:
        # -- leg 1: collective accounting ------------------------------
        sharded, _ = _run_fit_child('sharded', outdir)
        g = sharded['gauges']
        check(g.get('comm.all_reduce.bytes', 0) > 0 and
              g.get('comm.all_reduce.count', 0) > 0,
              'sharded 4x2 fit reports all-reduce traffic (%s bytes)'
              % g.get('comm.all_reduce.bytes'))
        check(g.get('comm.all_gather.bytes', 0) > 0 or
              g.get('comm.reduce_scatter.bytes', 0) > 0,
              'sharded 4x2 fit reports gather/scatter traffic')
        check(g.get('comm.bytes_per_step', 0) > 0,
              'comm.bytes_per_step > 0 (got %s)'
              % g.get('comm.bytes_per_step'))
        frac = g.get('perf.comm_fraction')
        check(frac is not None and 0.0 <= frac <= 1.0,
              'perf.comm_fraction in [0, 1] (got %s)' % frac)
        check(sharded['prom_has_fraction'],
              'perf.comm_fraction present in the Prometheus exposition')

        analytic, _ = _run_fit_child('analytic', outdir)
        g = analytic['gauges']
        dp = 4
        expect = 2.0 * (dp - 1) / dp * analytic['param_bytes']
        got = g.get('comm.all_reduce.wire_bytes', 0)
        check(abs(got - expect) <= 0.25 * expect + 256,
              'dp=4 gradient all-reduce wire bytes match '
              '(dp-1)/dp*2*param_bytes = %.0f (got %.0f)'
              % (expect, got))

        # -- leg 2: sharding inspector ---------------------------------
        degraded, stderr = _run_fit_child('degraded', outdir)
        check(len(degraded.get('degraded', [])) >= 2,
              'degraded fit recorded per-tensor reasons (%s)'
              % degraded.get('degraded'))
        check(degraded['counters'].get('mesh.degraded_params', 0) >= 2,
              'mesh.degraded_params counted (%s)'
              % degraded['counters'].get('mesh.degraded_params'))
        check('REPLICATED' in stderr or 'replicated' in stderr.lower(),
              'degradation warned once per fit (child stderr)')
        expl = subprocess.run(
            [sys.executable, os.path.join(_HERE, 'explain_sharding.py'),
             degraded['plan'], '--strict'],
            capture_output=True, text=True, timeout=120)
        check(expl.returncode == 2,
              'explain_sharding --strict flags the degraded plan '
              '(rc %d)' % expl.returncode)
        check('no tp-divisible dim' in expl.stdout,
              'explain_sharding surfaces the per-tensor reason')

        # -- leg 3: straggler attribution ------------------------------
        _run_skew_leg(outdir)
        with open(os.path.join(outdir, 'cluster_status.json')) as f:
            view = json.load(f)
        skew = (view['cluster'].get('gauges') or {}) \
            .get('cluster.step_skew', 0)
        laggard = view['cluster'].get('step_skew') or {}
        check(skew > 0.5 and laggard.get('rank') == 1,
              'cluster_status.json names rank 1 as the straggler '
              '(skew=%.2f, laggard=%s)' % (skew, laggard.get('rank')))
        with open(os.path.join(outdir, 'cluster_status.prom')) as f:
            prom = f.read()
        check('mxtpu_cluster_step_skew' in prom,
              'cluster.step_skew exposed in cluster_status.prom')
        check('mxtpu_comm_step_time_bucket' in prom,
              'per-rank comm.step_time histograms exposed in .prom')
        skew_rec = os.path.join(outdir, 'flightrec-rank0-skew.json')
        ok = False
        try:
            with open(skew_rec) as f:
                rec = json.load(f)
            ok = rec['reason'] == 'skew' and \
                rec['skew']['laggard']['rank'] == 1
        except Exception:
            ok = False
        check(ok, 'health plane flight-recorded the laggard (%s)'
              % skew_rec)
    finally:
        if not args.keep:
            shutil.rmtree(outdir, ignore_errors=True)

    if failures:
        print('\n%d check(s) FAILED' % len(failures), file=sys.stderr)
        return 1
    print('\ncommunication-plane smoke OK')
    return 0


if __name__ == '__main__':
    sys.exit(main())
