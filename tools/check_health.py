#!/usr/bin/env python
"""Training-health smoke: the whole observability plane end to end.

Spawns a 2-worker ``dist_async`` session in which every rank

- runs a short ``Module.fit`` with an injected-NaN batch and the
  on-device sentinels armed (``MXTPU_HEALTH_SENTINELS=1``, warn), then
- heartbeats its metrics to the rank-0 kv server and dumps its Chrome
  trace,

after which rank 0 asserts the merged cluster telemetry view contains
BOTH ranks (each with a nonzero ``health.nan_steps``), and rank 1 dies
at a fault-injected kill site so its flight recorder writes the
``injected-kill`` postmortem.  The parent then

- checks rank 1 exited by SIGKILL and its flight-recorder dump parses
  (valid JSON, spans + metrics present),
- merges the per-rank traces with ``tools/merge_traces.py`` (pid=rank)
  and validates the result with ``tools/check_trace.py``.

Run from the repo root::

    python tools/check_health.py

Exit code 0 on success — the CI guard for the docs/observability.md
health plane: if sentinels, heartbeat telemetry, the flight recorder or
trace merging silently break, one of the asserts trips.
"""
import argparse
import json
import os
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def worker(outdir):
    os.environ['XLA_FLAGS'] = os.environ.get('XLA_FLAGS', '') + \
        ' --xla_force_host_platform_device_count=2'
    import jax
    jax.config.update('jax_platforms', 'cpu')

    import numpy as np
    sys.path.insert(0, ROOT)
    import mxnet_tpu as mx
    from mxnet_tpu import instrument, resilience

    kv = mx.kv.create('dist_async')
    rank = kv.rank

    # -- a short fit with one injected-NaN batch: the sentinels must
    # flag it at a drain without any extra host syncs
    rng = np.random.RandomState(rank)
    bs, d, classes = 16, 10, 4
    X = rng.randn(6 * bs, d).astype(np.float32)
    Y = (X @ rng.randn(d, classes)).argmax(1).astype(np.float32)
    X[3 * bs + 1, 0] = np.nan
    net = mx.sym.Variable('data')
    net = mx.sym.FullyConnected(net, num_hidden=16, name='fc1')
    net = mx.sym.Activation(net, act_type='relu', name='act1')
    net = mx.sym.FullyConnected(net, num_hidden=classes, name='fc2')
    net = mx.sym.SoftmaxOutput(net, name='softmax')
    it = mx.io.NDArrayIter(data=X, label=Y, batch_size=bs, shuffle=False)
    mod = mx.mod.Module(net)
    mod.fit(it, num_epoch=1, optimizer='sgd', kvstore='local',
            optimizer_params={'learning_rate': 0.1},
            eval_metric='acc', initializer=mx.init.Uniform(0.05),
            batch_end_callback=mx.callback.Speedometer(bs, 2,
                                                       health=True))
    snap = instrument.metrics_snapshot()
    assert snap['counters'].get('health.nan_steps', 0) >= 1, \
        'rank %d: sentinel missed the injected NaN: %r' \
        % (rank, snap['counters'])
    assert snap['counters'].get('health.host_syncs', 0) == 0, \
        'rank %d: sentinels forced their own host syncs' % rank

    # -- let the heartbeat piggyback carry the counters, then check the
    # merged cluster view on rank 0
    kv.barrier()
    time.sleep(2.5)          # >= 2 beat intervals
    if rank == 0:
        view = kv.telemetry()
        got = sorted(view['ranks'])
        assert got == [0, 1], 'cluster view ranks: %r' % (got,)
        for r in (0, 1):
            nan = view['ranks'][r]['counters'].get('health.nan_steps', 0)
            assert nan >= 1, 'rank %d telemetry missing nan_steps' % r
        assert view['cluster']['counters'].get('health.nan_steps', 0) >= 2
        print('check_health: cluster view OK (%d ranks)' % len(got),
              flush=True)

    # -- per-rank trace for the merged timeline
    instrument.dump_trace(os.path.join(outdir,
                                       'trace_rank%d.json' % rank))
    kv.barrier()

    if rank == 1:
        # die at a fault-injected kill site: the flight recorder's
        # last-breath hook must leave the injected-kill postmortem
        resilience.set_faults('client.send.push:after:1:kill')
        kv.push(0, mx.nd.ones((2, 2)))
        time.sleep(10)
        raise AssertionError('rank 1 survived the injected kill')
    kv.init(0, mx.nd.zeros((2, 2)))
    time.sleep(2.0)          # outlive rank 1 so its beats/kill land
    kv.close()
    print('check_health worker rank %d OK' % rank, flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--worker', action='store_true', help=argparse.SUPPRESS)
    ap.add_argument('--outdir', default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.worker:
        worker(args.outdir)
        return 0

    import tempfile
    outdir = tempfile.mkdtemp(prefix='mxtpu_health_')
    port = 9890 + (os.getpid() * 13) % 40
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.pop('JAX_PLATFORMS', None)
        env.update({
            'MXTPU_PROCESS_ID': str(rank),
            'MXTPU_NUM_PROCESSES': '2',
            'MXTPU_KV_SERVER_ADDR': '127.0.0.1:%d' % port,
            'MXTPU_METRICS': '1',
            'MXTPU_PROFILE': '1',
            'MXTPU_HEALTH_SENTINELS': '1',
            'MXTPU_HEALTH_ACTION': 'warn',
            'MXTPU_FLIGHT_RECORDER': outdir,
            'MXTPU_KV_BARRIER_TIMEOUT': '90',
        })
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), '--worker',
             '--outdir', outdir], env=env))
    rcs = [p.wait(timeout=600) for p in procs]
    assert rcs[0] == 0, 'rank 0 failed (rc %r)' % (rcs[0],)
    assert rcs[1] == -signal.SIGKILL, \
        'rank 1 should die by injected SIGKILL, rc %r' % (rcs[1],)

    # rank 1's postmortem: written by the pre-kill hook, valid JSON
    with open(os.path.join(outdir, 'flightrec-rank1.json')) as f:
        rec = json.load(f)
    assert rec['reason'] == 'injected-kill', rec['reason']
    assert rec['spans'], 'flight recorder captured no spans'
    assert 'health.nan_steps' in rec['metrics']['counters'], \
        'flight recorder metrics missing health.*'
    print('check_health: flight recorder postmortem OK '
          '(%d spans, reason=%s)' % (len(rec['spans']), rec['reason']))

    # merged rank timeline validates
    merged = os.path.join(outdir, 'merged.json')
    rc = subprocess.call(
        [sys.executable, os.path.join(ROOT, 'tools', 'merge_traces.py'),
         '-o', merged,
         os.path.join(outdir, 'trace_rank0.json'),
         os.path.join(outdir, 'trace_rank1.json')])
    assert rc == 0, 'merge_traces/check_trace failed'
    with open(merged) as f:
        doc = json.load(f)
    pids = {e['pid'] for e in doc['traceEvents']}
    assert pids == {0, 1}, 'merged trace pids: %r' % (pids,)
    print('check_health: merged trace OK (%d events, pids=%s)'
          % (len(doc['traceEvents']), sorted(pids)))
    print('check_health OK')
    return 0


if __name__ == '__main__':
    sys.exit(main())
