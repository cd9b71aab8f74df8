#!/usr/bin/env python
"""Input-pipeline & goodput plane smoke — the acceptance gate of the
docs/observability.md "input-pipeline & goodput plane" (hermetic: the
parent never imports jax; children pin their own CPU backend).

Two legs, one synthetic JPEG record file through the FULL iterator
chain (ImageRecordIter -> PrefetchingIter -> DeviceFeedIter, the
product path's data plumbing) feeding a tiny ``Module.fit`` under
``MXTPU_IOWATCH=1``:

1. **Baseline**: every pipeline stage histogram
   (``iowatch.stage.read/decode/batchify/prefetch_wait/feed_wait/
   device_stage``) is nonzero — each link of the chain attributed its
   time — and the goodput ledger's exclusive buckets sum to fit wall
   clock within tolerance.

2. **Verdict flip**: the same fit under
   ``MXTPU_FAULTS='io.read:delay:1:SECS'`` (the ``io.read`` fault site
   inside the record producer) must turn the run input-bound —
   ``tools/explain_goodput.py`` names ``input_stall`` as the dominant
   badput source AND ``read`` as the slowest pipeline stage, its
   ``--strict`` floor separates the two runs (baseline passes, faulted
   exits 2).

Usage: ``python tools/check_io.py [--keep]``.  Exits nonzero on any
failed assertion.  CPU-safe;
run by ``tests/test_iowatch.py`` under tier-1 and by hand after
touching the iterator chain or the goodput ledger.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)

# every link of the iterator chain must attribute time here
EXPECTED_STAGES = ('read', 'decode', 'batchify', 'prefetch_wait',
                   'feed_wait', 'device_stage')


# ---------------------------------------------------------------------------
# child: one fit through the full chain
# ---------------------------------------------------------------------------

def _child(outdir, mode, batches=6, batch_size=8, side=24, epochs=2):
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import numpy as np
    sys.path.insert(0, _REPO)
    import mxnet_tpu as mx
    from mxnet_tpu import instrument, iowatch, recordio
    from mxnet_tpu.io_record import ImageRecordIter

    # synthetic record file: structured patterns JPEG-compress
    # realistically (pure noise inflates decode cost)
    rng = np.random.RandomState(0)
    rec_path = os.path.join(outdir, 'synth.rec')
    rec = recordio.MXRecordIO(rec_path, 'w')
    yy, xx = np.mgrid[0:side, 0:side]
    for i in range(batches * batch_size):
        img = np.stack([
            (127 + 120 * np.sin(xx / (3.0 + i % 7) + i)),
            (127 + 120 * np.cos(yy / (2.0 + i % 5))),
            rng.randint(0, 255, (side, side)),
        ], axis=2).astype(np.uint8)
        header = recordio.IRHeader(0, float(i % 10), i, 0)
        rec.write(recordio.pack_img(header, img, quality=85))
    rec.close()

    it = ImageRecordIter(path_imgrec=rec_path,
                         data_shape=(3, side, side),
                         batch_size=batch_size,
                         preprocess_threads=2, prefetch_buffer=2)
    it = mx.io.PrefetchingIter(it)   # fit adds the DeviceFeedIter wrap

    net = mx.sym.Variable('data')
    net = mx.sym.Flatten(net, name='flat')
    net = mx.sym.FullyConnected(net, num_hidden=10, name='fc')
    net = mx.sym.SoftmaxOutput(net, name='softmax')
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=epochs, optimizer='sgd',
            optimizer_params={'learning_rate': 0.05},
            initializer=mx.init.Uniform(0.05))

    instrument.dump_metrics(os.path.join(outdir,
                                         'metrics_%s.json' % mode))
    snap = instrument.metrics_snapshot()
    stages = {k[len('iowatch.stage.'):]: v.get('count', 0)
              for k, v in (snap.get('histograms') or {}).items()
              if k.startswith('iowatch.stage.')}
    print('RESULT|' + json.dumps({
        'mode': mode,
        'stages': stages,
        'goodput': iowatch.goodput_snapshot(),
    }), flush=True)


# ---------------------------------------------------------------------------
# parent
# ---------------------------------------------------------------------------

def _run_child(outdir, mode, extra_env=None, timeout=420):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith('MXTPU_')}
    env.update({'MXTPU_IOWATCH': '1', 'MXTPU_DEVICE_FEED': '1'})
    env.update(extra_env or {})
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         '--run-child', mode, '--outdir', outdir],
        capture_output=True, text=True, timeout=timeout, env=env)
    if out.returncode != 0:
        raise RuntimeError('%s child failed (rc %d):\n%s' %
                           (mode, out.returncode, out.stderr[-2000:]))
    for line in out.stdout.splitlines():
        if line.startswith('RESULT|'):
            return json.loads(line[len('RESULT|'):])
    raise RuntimeError('%s child printed no RESULT line:\n%s'
                       % (mode, out.stdout[-2000:]))


def _explain(metrics_path, strict_floor=None):
    """Run tools/explain_goodput.py; return (rc, stdout)."""
    cmd = [sys.executable, os.path.join(_HERE, 'explain_goodput.py'),
           metrics_path]
    if strict_floor is not None:
        cmd += ['--strict', '--floor', '%.6f' % strict_floor]
    out = subprocess.run(cmd, capture_output=True, text=True,
                         timeout=120)
    return out.returncode, out.stdout + out.stderr


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--keep', action='store_true',
                    help='keep the scratch dir (prints its path)')
    ap.add_argument('--fault-delay', type=float, default=0.08,
                    help='per-read injected delay seconds (default '
                         '%(default)s)')
    ap.add_argument('--run-child', default=None, help=argparse.SUPPRESS)
    ap.add_argument('--outdir', default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.run_child:
        _child(args.outdir, args.run_child)
        return 0

    assert 'jax' not in sys.modules, \
        'check_io parent must stay jax-free'
    outdir = tempfile.mkdtemp(prefix='mxtpu_check_io_')
    failures = []

    def check(cond, msg):
        print('%s %s' % ('OK  ' if cond else 'FAIL', msg))
        if not cond:
            failures.append(msg)

    try:
        base = _run_child(outdir, 'baseline')
        gp = base['goodput']

        # leg 1: every stage attributed
        for stage in EXPECTED_STAGES:
            check(base['stages'].get(stage, 0) > 0,
                  'iowatch.stage.%s nonzero (got %s)'
                  % (stage, base['stages'].get(stage, 0)))
        wall = gp.get('wall_secs', 0.0)
        total = gp.get('productive_secs', 0.0) + \
            sum(gp.get('buckets', {}).values())
        check(wall > 0, 'goodput ledger saw wall clock (%.3fs)' % wall)
        check(abs(total - wall) <= 0.05 * wall + 1e-6,
              'buckets + productive sum to wall within 5%% '
              '(%.3fs vs %.3fs)' % (total, wall))
        check(0.0 < gp.get('fraction', 0.0) <= 1.0,
              'goodput fraction in (0, 1] (%.3f)'
              % gp.get('fraction', 0.0))

        # leg 2: injected read delay flips the verdict to input-bound.
        # One escalation retry: on an oversubscribed host the decode
        # threads' measured wall time (preemption counts) can
        # transiently out-fatten the injected read delay, so a miss
        # re-runs with 3x the delay before counting as a failure.
        for attempt in range(2):
            delay = args.fault_delay * (3 ** attempt)
            fault = _run_child(
                outdir, 'fault',
                extra_env={'MXTPU_FAULTS': 'io.read:delay:1:%g' % delay})
            fgp = fault['goodput']
            rc, txt = _explain(os.path.join(outdir, 'metrics_fault.json'))
            if 'slowest pipeline stage: read' in txt:
                break
            if attempt == 0:
                print('.... read not the fattest stage under host load; '
                      'retrying with delay %g' % (args.fault_delay * 3))
        check(fgp.get('fraction', 1.0) < gp.get('fraction', 0.0),
              'injected read delay lowered goodput (%.3f -> %.3f)'
              % (gp.get('fraction', 0.0), fgp.get('fraction', 1.0)))
        buckets = fgp.get('buckets', {})
        check(buckets and max(sorted(buckets),
                              key=lambda b: buckets[b]) ==
              'input_stall',
              'dominant badput bucket is input_stall (buckets: %s)'
              % {k: round(v, 3) for k, v in buckets.items()})
        check(rc == 0 and 'dominant badput: input_stall' in txt,
              'explain_goodput names input_stall as dominant')
        check('slowest pipeline stage: read' in txt,
              'explain_goodput names the read stage')

        # --strict floor separates the two runs
        floor = (gp.get('fraction', 0.0) +
                 fgp.get('fraction', 0.0)) / 2.0
        rc_base, _ = _explain(
            os.path.join(outdir, 'metrics_baseline.json'),
            strict_floor=floor)
        rc_fault, _ = _explain(
            os.path.join(outdir, 'metrics_fault.json'),
            strict_floor=floor)
        check(rc_base == 0,
              'strict floor %.3f passes the baseline (rc %d)'
              % (floor, rc_base))
        check(rc_fault == 2,
              'strict floor %.3f rejects the faulted run (rc %d)'
              % (floor, rc_fault))
    finally:
        if args.keep:
            print('scratch kept: %s' % outdir)
        else:
            shutil.rmtree(outdir, ignore_errors=True)

    if failures:
        print('\n%d check(s) FAILED' % len(failures), file=sys.stderr)
        return 1
    print('\ninput-pipeline smoke OK')
    return 0


if __name__ == '__main__':
    sys.exit(main())
