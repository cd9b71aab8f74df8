#!/usr/bin/env python
"""Hermetic dp×tp sharded-fit smoke on 8 VIRTUAL devices
(docs/parallel.md — the product-path acceptance gate).

Parent mode (default) orchestrates child interpreters, each started
with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` and
``JAX_PLATFORMS=cpu`` so the dp×tp mesh code runs without hardware
(the same stand-in the test suite's conftest uses), and asserts:

- **oracle parity** — a ``Module.fit(mesh='4x2', partition='auto')``
  run (ZeRO-sharded optimizer state, tp-sharded params, gradient
  reductions inside the compiled program) trains to the same
  parameters as a plain single-device fit, within float tolerance:
  the mesh is a LAYOUT, never a different model;
- **1×1 identity** — ``mesh='1x1'`` is bit-for-bit the unsharded fused
  fit (params and final train-metric value), the depth-1 regression
  discipline of docs/performance.md;
- **warm sharded start** — with a shared MXTPU_COMPILE_CACHE, a second
  sharded fit replays the (batch_sig, mesh_sig)-keyed manifest through
  the AOT warmup pool and takes ZERO hot-path traces
  (``executor.xla_traces == 0``, ``compile.aot_calls > 0``);
- **MFU sanity** — ``perf.mfu`` stays in [0, 1] with
  ``perf.num_devices == 8`` (per-device vs global FLOPs accounting,
  perfwatch.note_step);
- **collective accounting** (MXTPU_COMMWATCH, commwatch.py) — the
  sharded fit reports nonzero all-reduce + gather/scatter bytes and a
  ``perf.comm_fraction`` in [0, 1]; a ``dp=4, tp=1, replicated`` fit's
  gradient all-reduce wire bytes match the analytic ring formula
  ``(dp-1)/dp · 2 · param_bytes`` within tolerance; and ``mesh=1x1``
  reports ZERO collective bytes — the accounting never invents traffic
  a single device cannot have.

Usage: ``python tools/check_multichip.py [--dir D] [--keep]``
Exits nonzero on any failed assertion.  CPU-safe; run by
``tests/test_multichip_fit.py`` and by hand after touching the
sharded-fit path.
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

MESH = '4x2'
PARTITION = 'auto'


def _child(mode):
    """One tiny fit; prints a JSON line of params + counters/gauges.

    Modes: 'oracle' (no mesh), 'oneone' (mesh=1x1), 'sharded'
    (mesh=4x2, cold), 'warm' (mesh=4x2, manifest replay), 'commrep'
    (mesh=4x1 replicated — the analytic gradient-all-reduce case).
    """
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import numpy as np
    sys.path.insert(0, _REPO)
    import mxnet_tpu as mx
    from mxnet_tpu import instrument

    instrument.set_metrics(True)

    net = mx.sym.Variable('data')
    net = mx.sym.FullyConnected(net, num_hidden=32, name='fc1')
    net = mx.sym.Activation(net, act_type='relu', name='act1')
    net = mx.sym.FullyConnected(net, num_hidden=8, name='fc2')
    net = mx.sym.SoftmaxOutput(net, name='softmax')

    rng = np.random.RandomState(0)
    rows = 128
    X = rng.randn(rows, 16).astype(np.float32)
    Y = (rng.rand(rows) * 8).astype(np.float32)
    batch_size = 64
    it = mx.io.NDArrayIter(X, Y, batch_size=batch_size)

    mesh = {'oracle': None, 'oneone': '1x1',
            'commrep': '4x1'}.get(mode, MESH)
    partition = None if mesh in (None, '1x1', '4x1') else PARTITION

    mx.random.seed(11)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=2, optimizer='sgd',
            optimizer_params={'learning_rate': 0.1, 'momentum': 0.9},
            eval_metric='acc', initializer=mx.init.Uniform(0.05),
            mesh=mesh, partition=partition)

    out = {'mode': mode, 'fused': mod._fused is not None}
    # counters snapshot BEFORE the score pass below: the zero-hot-path
    # contract is about the FIT loop (score's inference forward traces
    # its own jit program, legitimately)
    snap = instrument.metrics_snapshot()
    out['counters'] = snap['counters']
    out['gauges'] = {k: v for k, v in snap['gauges'].items()
                     if k.startswith(('perf.', 'comm.'))
                     and '[' not in k}
    # total trainable-parameter bytes: the analytic gradient-all-reduce
    # formula's N (everything here is f32 and trainable)
    arg_params, _ = mod.get_params()
    out['param_bytes'] = int(sum(
        int(np.prod(v.shape)) * 4 for v in arg_params.values()))
    out['params'] = {k: np.asarray(v.asnumpy(), np.float64)
                     .reshape(-1).tolist()
                     for k, v in sorted(arg_params.items())}
    metric = mx.metric.create('acc')
    # deterministic final-state metric over the train set (the
    # 1x1-vs-unsharded identity check compares it too)
    out['score'] = dict(mod.score(
        mx.io.NDArrayIter(X, Y, batch_size=batch_size), metric))
    print(json.dumps(out))


def _run_child(mode, cache_dir=None, warm=False):
    env = dict(os.environ)
    flags = env.get('XLA_FLAGS', '')
    if 'xla_force_host_platform_device_count' not in flags:
        env['XLA_FLAGS'] = \
            flags + ' --xla_force_host_platform_device_count=8'
    env['JAX_PLATFORMS'] = 'cpu'
    # the peak tables hold real chips only: nominal figures keep
    # perf.mfu / perf.comm_fraction defined on the virtual CPU devices
    env.setdefault('MXTPU_PEAK_FLOPS', '2e11')
    env.setdefault('MXTPU_PEAK_BW', '1e10')
    env['MXTPU_METRICS'] = '1'
    env['MXTPU_PERFWATCH'] = '1'
    env['MXTPU_COMMWATCH'] = '1'
    env['MXTPU_WARM_START'] = '1' if warm else '0'
    if cache_dir is not None:
        env['MXTPU_COMPILE_CACHE'] = cache_dir
    else:
        env.pop('MXTPU_COMPILE_CACHE', None)
    env.pop('MXTPU_MESH', None)
    env.pop('MXTPU_PARTITION', None)
    out = subprocess.run([sys.executable, os.path.abspath(__file__),
                          '--run-child', mode], env=env,
                         capture_output=True, text=True, timeout=900)
    if out.returncode != 0:
        print(out.stdout)
        print(out.stderr, file=sys.stderr)
        raise RuntimeError('%s child failed (rc %d)'
                           % (mode, out.returncode))
    return json.loads(out.stdout.strip().splitlines()[-1])


def _max_abs_diff(pa, pb):
    worst = 0.0
    for k in pa:
        for a, b in zip(pa[k], pb[k]):
            worst = max(worst, abs(a - b))
    return worst


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--run-child', default=None,
                    help='internal: run one fit mode and print JSON')
    ap.add_argument('--dir', default=None,
                    help='compile-cache dir (default: fresh temp dir)')
    ap.add_argument('--keep', action='store_true')
    args = ap.parse_args(argv)

    if args.run_child:
        _child(args.run_child)
        return 0

    cache_dir = args.dir or tempfile.mkdtemp(prefix='mxtpu_multichip_')
    failures = []

    def check(cond, msg):
        print('%s %s' % ('OK  ' if cond else 'FAIL', msg))
        if not cond:
            failures.append(msg)

    try:
        oracle = _run_child('oracle')
        oneone = _run_child('oneone')
        cold = _run_child('sharded', cache_dir=cache_dir)
        warm = _run_child('sharded', cache_dir=cache_dir, warm=True)

        check(all(r['fused'] for r in (oracle, oneone, cold, warm)),
              'every run took the fused fit path')

        diff = _max_abs_diff(oracle['params'], cold['params'])
        check(diff < 1e-4,
              'sharded (%s, %s) params match the single-device oracle '
              '(max |diff| %.3g)' % (MESH, PARTITION, diff))

        check(oracle['params'] == oneone['params'],
              'mesh=1x1 params are bit-for-bit the unsharded fit')
        check(oracle['score'] == oneone['score'],
              'mesh=1x1 metric value equals the unsharded fit (%s)'
              % (oneone['score'],))

        wc = warm['counters']
        check(wc.get('executor.xla_traces', 0) == 0,
              'warm sharded fit took ZERO hot-path traces (got %s)'
              % wc.get('executor.xla_traces', 0))
        check(wc.get('compile.warmup_traces', 0) > 0,
              'warm traces ran on the warmup pool (%s)'
              % wc.get('compile.warmup_traces', 0))
        check(wc.get('compile.aot_calls', 0) > 0,
              'warm sharded fit ran from AOT executables (%s calls)'
              % wc.get('compile.aot_calls', 0))
        check(wc.get('compile.cache_hits', 0) > 0,
              'warm executables came from the persistent cache (%s)'
              % wc.get('compile.cache_hits', 0))
        check(cold['params'] == warm['params'],
              'cold and warm sharded fits train to identical params')

        try:
            with open(os.path.join(cache_dir, 'manifest.json')) as f:
                traces = json.load(f)['traces']
        except Exception:
            traces = []
        mesh_entries = [t for t in traces if t.get('kind') == 'fit_step'
                        and (t.get('meta') or {}).get('mesh')]
        check(len(mesh_entries) > 0,
              'manifest keys fit_step entries on the mesh sig (%s)'
              % [(t['meta']['mesh']) for t in mesh_entries[:1]])

        for name, run in (('cold', cold), ('warm', warm)):
            g = run['gauges']
            mfu = g.get('perf.mfu')
            check(mfu is not None and 0.0 <= mfu <= 1.0,
                  '%s perf.mfu in [0, 1] (got %s)' % (name, mfu))
            check(g.get('perf.num_devices') == 8,
                  '%s perf.num_devices == 8 (got %s)'
                  % (name, g.get('perf.num_devices')))

        # -- collective accounting (MXTPU_COMMWATCH, commwatch.py) ----
        commrep = _run_child('commrep', cache_dir=cache_dir)
        for name, run in (('cold', cold), ('warm', warm)):
            g = run['gauges']
            check(g.get('comm.all_reduce.count', 0) > 0 and
                  g.get('comm.all_reduce.bytes', 0) > 0,
                  '%s sharded fit reports all-reduce traffic '
                  '(count %s, bytes %s)'
                  % (name, g.get('comm.all_reduce.count'),
                     g.get('comm.all_reduce.bytes')))
            check(g.get('comm.all_gather.bytes', 0) > 0 or
                  g.get('comm.reduce_scatter.bytes', 0) > 0,
                  '%s sharded fit reports gather/scatter traffic'
                  % name)
            check(g.get('comm.bytes_per_step', 0) > 0,
                  '%s comm.bytes_per_step > 0 (got %s)'
                  % (name, g.get('comm.bytes_per_step')))
            frac = g.get('perf.comm_fraction')
            check(frac is not None and 0.0 <= frac <= 1.0,
                  '%s perf.comm_fraction in [0, 1] (got %s)'
                  % (name, frac))

        # dp=4 pure data parallelism: each device's gradient all-reduce
        # moves 2·(dp-1)/dp·param_bytes on the wire (ring schedule) —
        # the analytic formula the accounting must reproduce from the
        # compiled HLO (metric-delta scalar reduces ride along, hence
        # the tolerance)
        g = commrep['gauges']
        dp = 4
        expect = 2.0 * (dp - 1) / dp * commrep['param_bytes']
        got = g.get('comm.all_reduce.wire_bytes', 0)
        check(abs(got - expect) <= 0.25 * expect + 256,
              'dp=4 gradient all-reduce wire bytes match the analytic '
              '(dp-1)/dp * 2 * param_bytes = %.0f (got %.0f)'
              % (expect, got))
        diff = _max_abs_diff(oracle['params'], commrep['params'])
        check(diff < 1e-4,
              'commrep (4x1, replicated) params match the oracle '
              '(max |diff| %.3g)' % diff)

        g = oneone['gauges']
        zero_comm = not any(v for k, v in g.items()
                            if k.startswith('comm.') and
                            k.endswith(('.bytes', '.wire_bytes',
                                        '_per_step')))
        check(zero_comm,
              'mesh=1x1 reports ZERO collective bytes (%s)'
              % {k: v for k, v in g.items() if k.startswith('comm.')})
    finally:
        if not args.keep and args.dir is None:
            shutil.rmtree(cache_dir, ignore_errors=True)

    if failures:
        print('\n%d check(s) FAILED' % len(failures), file=sys.stderr)
        return 1
    print('\nmultichip sharded-fit smoke OK (8 virtual devices, mesh %s)'
          % MESH)
    return 0


if __name__ == '__main__':
    sys.exit(main())
