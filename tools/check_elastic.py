#!/usr/bin/env python
"""Elastic self-healing smoke: kill a worker mid-epoch and assert the
job REPAIRS itself (docs/resilience.md "elastic membership & repair").

Two hermetic legs, each a real 2-worker ``Module.fit`` over dist_async
with MXTPU_ELASTIC on, per-rank checkpoints, and the goodput ledger
open; rank 1 is SIGKILLed mid-epoch by a deterministic MXTPU_FAULTS
directive on its push stream:

- **spare**: a replacement worker launched with ``MXTPU_ELASTIC_JOIN=1``
  parks in the join RPC, adopts the vacated rank when the server evicts
  it, re-seeds from the checkpoint consensus + a live-store param pull,
  and enters the fit loop at the cluster's current epoch.  The job
  finishes on the replacement and the final server params land within
  tolerance of a never-killed oracle run.
- **shrink**: no spare; after MXTPU_ELASTIC_WAIT the survivor commits
  the generation-gated resize and completes every epoch one worker
  down, without stalling.

Both legs assert the goodput ledger priced the repair: the
``recovery`` bucket is nonzero and the waterfall identity
``wall == productive + Σ badput`` holds exactly; and both measure
``recovery_time_secs`` — injected kill to the first post-repair
productive step (the ``elastic.post_repair_step_at`` gauge).

Run from the repo root::

    python tools/check_elastic.py [--mode spare|shrink|both]

Exit code 0 on success.
"""
import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EPOCHS = 7
BATCHES = 6            # per epoch (96 samples / bs 16)
BATCH_SLEEP = 0.12     # per-batch pacing so epochs outlast detection
# 4 params (fc1/fc2 weight+bias) -> 4 push frames per batch: the 30th
# outbound push is batch 8 = early in epoch 2 (deterministic mid-epoch
# kill)
KILL_PLAN = 'client.send.push:after:30:kill'
# oracle-vs-repaired tolerance: async apply-on-arrival plus the
# replacement re-running the killed rank's partial epoch makes exact
# parity impossible by construction; the bound is relative parameter
# distance, far inside the ~1.0 an independently-trained net shows
PARITY_REL = 0.5


# ---------------------------------------------------------------------------
# worker (child process)
# ---------------------------------------------------------------------------

def worker():
    os.environ['XLA_FLAGS'] = os.environ.get('XLA_FLAGS', '') + \
        ' --xla_force_host_platform_device_count=2'
    import jax
    jax.config.update('jax_platforms', 'cpu')

    import time as _time
    import numpy as np
    sys.path.insert(0, ROOT)
    import mxnet_tpu as mx
    from mxnet_tpu import instrument

    # joiners learn their rank from the join RPC (the store parks in
    # it until a vacancy opens), so the kv must exist before the data
    kv = mx.kv.create('dist_async')
    rank = kv.rank

    rng = np.random.RandomState(100 + rank)
    X = rng.rand(16 * BATCHES, 8).astype(np.float32)
    y = (rng.rand(16 * BATCHES) * 4).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=16)

    data = mx.sym.Variable('data')
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name='fc1')
    act = mx.sym.Activation(fc1, act_type='relu')
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name='fc2')
    net = mx.sym.SoftmaxOutput(fc2, name='softmax')

    prefix = os.path.join(os.environ['MXTPU_ELASTIC_CKPT'],
                          'rank%d' % rank, 'ck')
    os.makedirs(os.path.dirname(prefix), exist_ok=True)

    mx.random.seed(7)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=EPOCHS, kvstore=kv, optimizer='sgd',
            optimizer_params={'learning_rate': 0.02, 'momentum': 0.0},
            initializer=mx.init.Xavier(), checkpoint_prefix=prefix,
            batch_end_callback=lambda p: _time.sleep(BATCH_SLEEP))

    out = os.environ.get('MXTPU_ELASTIC_OUT')
    if out and rank == 0:
        # the SERVER's master copy is the job's final answer
        arg_params, _ = mod.get_params()
        final = {}
        for idx, name in enumerate(mod._param_names):
            buf = mx.nd.zeros(arg_params[name].shape)
            kv.pull(idx, out=buf)
            final[name] = buf.asnumpy()
        np.savez(out, **final)
    instrument.dump_metrics(os.environ['MXTPU_CHECK_METRICS_OUT'])
    kv.close()
    print('check_elastic worker rank %d OK' % rank, flush=True)


# ---------------------------------------------------------------------------
# driver (parent; jax-free)
# ---------------------------------------------------------------------------

def _base_env(port, outdir, tag, wait):
    env = dict(os.environ)
    env.pop('JAX_PLATFORMS', None)
    env.pop('MXTPU_FAULTS', None)
    env.pop('MXTPU_ELASTIC_JOIN', None)
    env.update({
        'MXTPU_NUM_PROCESSES': '2',
        'MXTPU_KV_SERVER_ADDR': '127.0.0.1:%d' % port,
        'MXTPU_METRICS': '1',
        'MXTPU_IOWATCH': '1',
        'MXTPU_ELASTIC': '1',
        'MXTPU_ELASTIC_WAIT': str(wait),
        'MXTPU_ELASTIC_POLL': '0.15',
        'MXTPU_KV_DEAD_TIMEOUT': '2.0',
        'MXTPU_KV_BARRIER_TIMEOUT': '120',
        'MXTPU_KV_RPC_TIMEOUT': '2.0',
        'MXTPU_ELASTIC_CKPT': os.path.join(outdir, tag, 'ck'),
        'MXTPU_ELASTIC_JOIN_TIMEOUT': '120',
    })
    return env


def _spawn(env, rank=None, joiner=False, faults=None, metrics_out=None,
           params_out=None):
    env = dict(env)
    if joiner:
        env['MXTPU_ELASTIC_JOIN'] = '1'
        env.pop('MXTPU_PROCESS_ID', None)
    else:
        env['MXTPU_PROCESS_ID'] = str(rank)
    if faults:
        env['MXTPU_FAULTS'] = faults
    env['MXTPU_CHECK_METRICS_OUT'] = metrics_out
    if params_out:
        env['MXTPU_ELASTIC_OUT'] = params_out
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), '--worker'],
        env=env, cwd=ROOT)


def _wait_all(procs, victim=None, timeout=240):
    """Wait out every process; returns {name: (rc, t_exit)}.  The
    victim's SIGKILL exit is expected; anything else nonzero fails."""
    out = {}
    t_end = time.monotonic() + timeout
    for name, p in procs.items():
        try:
            p.wait(timeout=max(1, t_end - time.monotonic()))
        except subprocess.TimeoutExpired:
            p.kill()
            raise AssertionError('%s timed out' % name)
        out[name] = (p.returncode, time.time())
        if name == victim:
            assert p.returncode == -signal.SIGKILL, \
                'victim exited %r, not SIGKILL' % (p.returncode,)
        else:
            assert p.returncode == 0, '%s exited %d' % (name,
                                                        p.returncode)
    return out


def _load_metrics(path):
    with open(path) as f:
        return json.load(f)


def _assert_goodput_identity(m, want_recovery):
    g = m.get('gauges', {})
    wall = g.get('goodput.wall_secs')
    assert wall and wall > 0, 'no goodput ledger in the dump'
    # every published bucket gauge (the ledger writes all of them,
    # zeros included) — derived from the dump so this jax-free parent
    # needs no framework import
    buckets = {k[len('goodput.'):-len('_secs')]: v
               for k, v in g.items()
               if k.startswith('goodput.') and k.endswith('_secs')
               and k not in ('goodput.wall_secs',
                             'goodput.productive_secs')}
    assert 'recovery' in buckets, sorted(g)
    total = g.get('goodput.productive_secs', 0.0) + sum(buckets.values())
    assert abs(total - wall) < 1e-6 * max(1.0, wall), \
        'goodput identity broken: wall=%r vs productive+badput=%r' \
        % (wall, total)
    if want_recovery:
        assert buckets['recovery'] > 0, \
            'recovery bucket empty after a repair: %r' % (buckets,)
    return buckets


def _recovery_time(m, t_kill):
    t_step = m.get('gauges', {}).get('elastic.post_repair_step_at')
    assert t_step, 'elastic.post_repair_step_at gauge missing'
    dt = t_step - t_kill
    assert 0 < dt < 120, 'implausible recovery time %.1fs' % dt
    return dt


def _run_cluster(outdir, port, tag, wait, spare, faulted=True):
    """One cluster run; returns (metrics_by_rank, t_kill, params_path)."""
    env = _base_env(port, outdir, tag, wait)
    mdir = os.path.join(outdir, tag)
    os.makedirs(mdir, exist_ok=True)
    params_out = os.path.join(mdir, 'final.npz')
    procs = {}
    mpaths = {}
    for rank in (0, 1):
        mpaths['rank%d' % rank] = os.path.join(
            mdir, 'metrics_rank%d.json' % rank)
        procs['rank%d' % rank] = _spawn(
            env, rank=rank,
            faults=KILL_PLAN if (faulted and rank == 1) else None,
            metrics_out=mpaths['rank%d' % rank],
            params_out=params_out if rank == 0 else None)
    if spare:
        mpaths['spare'] = os.path.join(mdir, 'metrics_spare.json')
        procs['spare'] = _spawn(env, joiner=True,
                                metrics_out=mpaths['spare'])
    t_kill = None
    if faulted:
        procs['rank1'].wait(timeout=180)
        t_kill = time.time()
        assert procs['rank1'].returncode == -signal.SIGKILL, \
            'rank 1 exited %r, not the injected SIGKILL' \
            % (procs['rank1'].returncode,)
    _wait_all(procs, victim='rank1' if faulted else None)
    metrics = {n: _load_metrics(p) for n, p in mpaths.items()
               if os.path.exists(p)}
    return metrics, t_kill, params_out


def _final_params(path):
    import numpy as np
    with np.load(path) as z:
        return {k: z[k] for k in z.files}


def run_spare(outdir, port):
    print('--- spare leg: kill rank 1, replacement joins ---',
          file=sys.stderr)
    metrics, t_kill, params = _run_cluster(outdir, port, 'spare',
                                           wait=60.0, spare=True)
    m0 = metrics['rank0']
    c0 = m0.get('counters', {})
    assert c0.get('kvstore.evictions', 0) >= 1, c0
    assert c0.get('kvstore.joins', 0) >= 1, c0
    assert not c0.get('kvstore.resizes', 0), \
        'spare leg must repair by join, not shrink: %r' % c0
    assert c0.get('elastic.repairs', 0) >= 1, c0
    _assert_goodput_identity(m0, want_recovery=True)
    # the replacement really re-seeded and trained
    cs = metrics['spare'].get('counters', {})
    assert cs.get('kvstore.rejoins', 0) >= 1, cs
    assert cs.get('fit.batches', 0) >= 1, \
        'the replacement never trained: %r' % cs
    _assert_goodput_identity(metrics['spare'], want_recovery=False)
    rec = _recovery_time(m0, t_kill)

    print('--- spare leg: never-killed oracle ---', file=sys.stderr)
    ometrics, _, oparams = _run_cluster(outdir, port + 1, 'oracle',
                                        wait=60.0, spare=False,
                                        faulted=False)
    import numpy as np
    got, want = _final_params(params), _final_params(oparams)
    assert set(got) == set(want), (sorted(got), sorted(want))
    worst = 0.0
    for k in sorted(want):
        rel = float(np.linalg.norm(got[k] - want[k])
                    / (np.linalg.norm(want[k]) + 1e-12))
        worst = max(worst, rel)
        print('  param %-12s rel-dist to oracle %.4f' % (k, rel),
              file=sys.stderr)
    assert worst < PARITY_REL, \
        'repaired params drifted %.3f from the oracle (bound %.2f)' \
        % (worst, PARITY_REL)
    print('spare leg OK: recovery %.2fs, worst param rel-dist %.4f'
          % (rec, worst), file=sys.stderr)
    return rec


def run_shrink(outdir, port):
    print('--- shrink leg: kill rank 1, no spare, dp-shrink ---',
          file=sys.stderr)
    metrics, t_kill, _ = _run_cluster(outdir, port, 'shrink',
                                      wait=1.0, spare=False)
    m0 = metrics['rank0']
    c0 = m0.get('counters', {})
    assert c0.get('kvstore.evictions', 0) >= 1, c0
    assert c0.get('kvstore.resizes', 0) >= 1, c0
    assert c0.get('elastic.shrinks', 0) >= 1, c0
    assert c0.get('elastic.repairs', 0) >= 1, c0
    # the epoch completed: all batches of all epochs ran on rank 0
    assert c0.get('fit.batches', 0) == EPOCHS * BATCHES, c0
    buckets = _assert_goodput_identity(m0, want_recovery=True)
    rec = _recovery_time(m0, t_kill)
    print('shrink leg OK: recovery %.2fs (ledger recovery bucket '
          '%.2fs)' % (rec, buckets['recovery']), file=sys.stderr)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--mode', choices=('spare', 'shrink', 'both'),
                    default='both')
    ap.add_argument('--worker', action='store_true',
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.worker:
        worker()
        return 0

    port = 9850 + (os.getpid() * 13) % 60
    outdir = tempfile.mkdtemp(prefix='mxtpu_elastic_')
    if args.mode in ('shrink', 'both'):
        run_shrink(outdir, port)
    if args.mode in ('spare', 'both'):
        run_spare(outdir, port + 3)
    print('check_elastic OK (%s)' % args.mode)
    return 0


if __name__ == '__main__':
    sys.exit(main())
