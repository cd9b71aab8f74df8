#!/usr/bin/env python
"""Resilience smoke: a 2-worker dist_async kvstore session under
injected faults (MXTPU_FAULTS drops a quarter of push frames and severs
the connection once mid-stream), asserting that

- training arithmetic converges exactly (no lost or double-applied
  pushes despite drops, a reconnect, and replay), and
- the recovery machinery actually fired: the per-rank instrument
  metrics dumps show nonzero ``kvstore.retries`` / ``kvstore.reconnects``
  / ``kvstore.push_replays``.

Run from the repo root::

    python tools/check_resilience.py [--pushes N]

Exit code 0 on success.  This is the CI guard for docs/resilience.md —
if a refactor silently breaks replay or reconnect, the convergence
assert or the nonzero-metrics assert trips.
"""
import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FAULT_PLAN = 'client.send.push:drop:0.25;client.send.push:after:9:sever'


def worker(pushes):
    os.environ['XLA_FLAGS'] = os.environ.get('XLA_FLAGS', '') + \
        ' --xla_force_host_platform_device_count=2'
    import jax
    jax.config.update('jax_platforms', 'cpu')

    import numpy as np
    sys.path.insert(0, ROOT)
    import mxnet_tpu as mx
    from mxnet_tpu import instrument

    kv = mx.kv.create('dist_async')
    rank, nworker = kv.rank, kv.num_workers
    shape = (3, 4)
    kv.init(7, mx.nd.zeros(shape))
    kv.set_optimizer(mx.optimizer.Test(rescale_grad=1.0))
    for _ in range(pushes):
        kv.push(7, mx.nd.ones(shape))
    kv.barrier()                # flush-then-barrier: replay + all applied
    out = mx.nd.zeros(shape)
    kv.pull(7, out=out)
    expected = pushes * nworker
    got = out.asnumpy()
    assert np.allclose(got, expected), \
        'rank %d: pulled %r, expected %d' % (rank, got.ravel()[:4], expected)
    kv.barrier()
    instrument.dump_metrics(os.environ['MXTPU_CHECK_METRICS_OUT'])
    undelivered = kv.close()
    assert not undelivered, \
        'rank %d: %d pushes undelivered' % (rank, undelivered)
    print('check_resilience worker rank %d OK' % rank, flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--pushes', type=int, default=25)
    ap.add_argument('--workers', type=int, default=2)
    ap.add_argument('--worker', action='store_true', help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.worker:
        worker(args.pushes)
        return 0

    import tempfile
    port = 9950 + (os.getpid() * 17) % 40
    outdir = tempfile.mkdtemp(prefix='mxtpu_resilience_')
    procs = []
    metric_paths = []
    for rank in range(args.workers):
        env = dict(os.environ)
        env.pop('JAX_PLATFORMS', None)
        mpath = os.path.join(outdir, 'metrics_rank%d.json' % rank)
        metric_paths.append(mpath)
        env.update({
            'MXTPU_PROCESS_ID': str(rank),
            'MXTPU_NUM_PROCESSES': str(args.workers),
            'MXTPU_KV_SERVER_ADDR': '127.0.0.1:%d' % port,
            'MXTPU_FAULTS': FAULT_PLAN,
            'MXTPU_FAULTS_SEED': str(11 + rank),
            'MXTPU_METRICS': '1',
            'MXTPU_KV_RPC_TIMEOUT': '1.0',
            'MXTPU_KV_RETRY_BASE': '0.05',
            'MXTPU_KV_RETRY_MAX': '0.5',
            'MXTPU_CHECK_METRICS_OUT': mpath,
        })
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), '--worker',
             '--pushes', str(args.pushes)],
            env=env, cwd=ROOT))
    rc = 0
    for rank, p in enumerate(procs):
        try:
            p.wait(timeout=300)
        except subprocess.TimeoutExpired:
            p.kill()
            print('FAIL: rank %d timed out' % rank)
            rc = 1
            continue
        if p.returncode != 0:
            print('FAIL: rank %d exited %d' % (rank, p.returncode))
            rc = 1
    if rc:
        return rc

    recovered = {'kvstore.retries': 0, 'kvstore.reconnects': 0,
                 'kvstore.push_replays': 0, 'kvstore.rpc_timeouts': 0}
    for mpath in metric_paths:
        with open(mpath) as f:
            counters = json.load(f).get('counters', {})
        for k in recovered:
            recovered[k] += counters.get(k, 0)
    print('recovery metrics:', json.dumps(recovered))
    assert recovered['kvstore.retries'] > 0, \
        'faults were injected but kvstore.retries stayed 0'
    assert recovered['kvstore.push_replays'] > 0, \
        'faults were injected but no pushes were replayed'
    print('check_resilience OK: convergence exact under %r' % FAULT_PLAN)
    return 0


if __name__ == '__main__':
    sys.exit(main())
