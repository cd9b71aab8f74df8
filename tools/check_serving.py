#!/usr/bin/env python
"""Serving-plane smoke: the whole docs/serving.md contract end to end.

The parent spawns one worker subprocess (fresh interpreter: registry,
knobs and threads start clean, like a real server process) with metrics
+ profiling on, in which

1. a synthetic checkpoint is loaded into a ``ModelServer`` and hammered
   by concurrent closed-loop clients (``tools/serve_bench.py`` driver);
   the worker asserts requests were genuinely COALESCED — more
   ``serving.batched_requests`` than ``serving.flushes`` — and that at
   least one multi-request flush padded up to a pow2 bucket;
2. every response is checked bit-for-bit against single-request
   ``Predictor.forward`` on a private oracle Predictor;
3. queue-wait/execute/e2e p50/p99 are asserted recorded and present in
   the ``instrument.render_prometheus`` exposition (``_bucket``/
   ``_sum``/``_count`` samples);
4. a tiny-queue server is driven into overload with the batcher paused:
   submit must shed with ``ServerOverloadedError``, ``serving.shed_total``
   must count it, and the queue must never exceed its bound;
5. the model is hot-reloaded with re-scaled params mid-traffic: no
   request may error, and responses must flip to the new params;
6. the worker dumps its Chrome trace, which the parent validates with
   ``tools/check_trace.py``.

Run from the repo root::

    python tools/check_serving.py

Exit code 0 on success — the CI guard for the serving plane.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import threading

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def worker(outdir):
    import jax
    jax.config.update('jax_platforms', 'cpu')

    import mxnet_tpu  # noqa: F401 - full package wiring
    from mxnet_tpu import instrument
    from mxnet_tpu.predictor import Predictor
    from mxnet_tpu.serving import ModelServer, ServerOverloadedError
    sys.path.insert(0, os.path.join(ROOT, 'tools'))
    import serve_bench

    assert instrument.metrics_enabled(), 'worker needs MXTPU_METRICS=1'

    prefix, shapes = serve_bench.build_synthetic_checkpoint(outdir)
    with open('%s-symbol.json' % prefix) as f:
        sym_json = f.read()
    from mxnet_tpu import ndarray as nd
    params = nd.load('%s-0001.params' % prefix)

    server = ModelServer(max_delay_ms=5.0)
    server.load_model('clf', prefix=prefix, epoch=1, input_shapes=shapes)
    oracle = Predictor(sym_json, params, dict(shapes), pad_to_bucket=True)

    # -- 1: deterministic coalesce — one flush, bit-for-bit sliced ----------
    # pause the batcher, queue 5 singles, resume: they must merge into
    # ONE flush whose outputs, sliced row-for-row, equal direct
    # Predictor.forward of the SAME merged rows (same pow2 bucket, same
    # compiled program — the batcher adds nothing numerically).
    rng = np.random.RandomState(0)
    d_in = shapes['data'][1]
    singles = [rng.rand(1, d_in).astype(np.float32) for _ in range(5)]
    server.pause('clf')
    futs = [server.submit('clf', data=x) for x in singles]
    server.resume('clf')
    got_rows = [f.result(timeout=30)[0] for f in futs]
    oracle.forward(data=np.concatenate(singles))
    want = oracle.get_output(0)
    for i, row in enumerate(got_rows):
        assert np.array_equal(row, want[i:i + 1]), \
            'coalesced row %d diverged from direct predict' % i
    batcher = server._entry('clf').batcher
    assert batcher.last_flush_rows == 5 and \
        oracle._active_bucket == 8, \
        'expected one 5-row flush in the pow2-8 bucket, got %d rows' \
        % batcher.last_flush_rows

    # -- 2: concurrent load — every response bit-equal to the oracle --------
    # XLA may pick different (equally valid) kernels per bucket SIZE,
    # so the cross-check is bucket-aware: a response must bit-match the
    # single-request oracle padded to SOME pow2 bucket.  Within a
    # bucket, rows are content-independent (other requests sharing the
    # batch cannot perturb yours) — that is the serving guarantee.
    payloads = [rng.rand(1 + i % 3, d_in).astype(np.float32)
                for i in range(64)]
    oracle_by_bucket = []
    for x in payloads:
        outs = {}
        for b in (1, 2, 4, 8, 16, 32, 64):
            if b < x.shape[0]:
                continue
            padded = np.concatenate(
                [x, np.zeros((b - x.shape[0], d_in), np.float32)])
            oracle.forward(data=padded)
            outs[b] = oracle.get_output(0)[:x.shape[0]].copy()
        oracle_by_bucket.append(outs)

    mismatches = []
    lock = threading.Lock()

    def client(idxs):
        for i in idxs:
            got = server.predict('clf', data=payloads[i])[0]
            if not any(np.array_equal(got, w)
                       for w in oracle_by_bucket[i].values()):
                with lock:
                    mismatches.append(i)

    threads = [threading.Thread(target=client,
                                args=(range(k, len(payloads), 8),))
               for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not mismatches, \
        'responses diverged from single-request Predictor.forward ' \
        'at payloads %s' % mismatches[:8]

    snap = instrument.metrics_snapshot()['counters']
    assert snap.get('serving.requests', 0) >= len(payloads)
    assert snap.get('serving.flushes', 0) >= 1
    assert snap['serving.batched_requests'] > snap['serving.flushes'], \
        'no coalescing happened: %d requests in %d flushes' \
        % (snap['serving.batched_requests'], snap['serving.flushes'])
    # at least one flush merged several requests into a pow2 bucket
    batcher = server._entry('clf').batcher
    from mxnet_tpu.compile_cache import pad_to_bucket
    assert pad_to_bucket(batcher.last_flush_rows) in (1, 2, 4, 8, 16,
                                                      32, 64, 128)
    print('check_serving: coalescing OK (%d requests / %d flushes), '
          'responses bit-exact' % (snap['serving.batched_requests'],
                                   snap['serving.flushes']), flush=True)

    # -- 3: SLO histograms recorded + exported ------------------------------
    hists = instrument.metrics_snapshot()['histograms']
    for h in ('serving.queue_wait_secs', 'serving.execute_secs',
              'serving.e2e_secs'):
        assert hists[h]['count'] > 0, '%s never observed' % h
        assert hists[h]['p99'] >= hists[h]['p50'] > 0.0
    prom = instrument.render_prometheus()
    for line in ('mxtpu_serving_e2e_secs_bucket{le=',
                 'mxtpu_serving_e2e_secs_sum',
                 'mxtpu_serving_e2e_secs_count',
                 '# TYPE mxtpu_serving_e2e_secs histogram'):
        assert line in prom, 'Prometheus exposition missing %r' % line
    print('check_serving: p50/p99 histograms OK (e2e p99 %.2fms)'
          % (1e3 * hists['serving.e2e_secs']['p99']), flush=True)

    # -- 4: overload sheds instead of queueing unboundedly ------------------
    small = ModelServer(max_delay_ms=5.0, max_queue=4)
    small.load_model('tiny', symbol_json=sym_json, params=params,
                     input_shapes=shapes)
    small.pause('tiny')
    shed = 0
    futs = []
    for _ in range(32):
        try:
            futs.append(small.submit(
                'tiny', data=np.zeros((1, shapes['data'][1]),
                                      np.float32)))
        except ServerOverloadedError:
            shed += 1
    qdepth = len(small._entry('tiny').batcher._queue)
    small.resume('tiny')
    for f in futs:
        f.result(timeout=30)
    assert shed == 32 - 4, 'expected 28 sheds at queue bound 4, got %d' \
        % shed
    assert qdepth <= 4, 'queue grew past its bound: %d' % qdepth
    shed_total = instrument.metrics_snapshot()['counters'].get(
        'serving.shed_total', 0)
    assert shed_total >= shed
    small.close()
    print('check_serving: overload shed OK (%d sheds, bound held)'
          % shed, flush=True)

    # -- 5: hot reload mid-traffic ------------------------------------------
    stop = threading.Event()
    errors = []

    def traffic():
        x = payloads[0]
        while not stop.is_set():
            try:
                server.predict('clf', data=x)
            except Exception as e:     # noqa: BLE001 - recorded
                errors.append(e)
                return

    t = threading.Thread(target=traffic)
    t.start()
    before = server.predict('clf', data=payloads[0])[0]
    scaled = {k: (v * 2.0 if k.startswith('arg:') or ':' not in k else v)
              for k, v in params.items()}
    server.reload_model('clf', symbol_json=sym_json, params=scaled,
                        input_shapes=shapes)
    after = server.predict('clf', data=payloads[0])[0]
    stop.set()
    t.join()
    assert not errors, 'requests failed across reload: %r' % errors[:3]
    assert not np.array_equal(before, after), \
        'reload did not swap the executable'
    reloads = instrument.metrics_snapshot()['counters'].get(
        'serving.reloads', 0)
    assert reloads == 1
    print('check_serving: hot reload OK (traffic uninterrupted)',
          flush=True)

    server.close()
    instrument.dump_trace(os.path.join(outdir, 'serve_trace.json'))
    print('check_serving worker OK', flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--worker', action='store_true', help=argparse.SUPPRESS)
    ap.add_argument('--outdir', default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.worker:
        worker(args.outdir)
        return 0

    outdir = tempfile.mkdtemp(prefix='mxtpu_serving_')
    env = dict(os.environ)
    env.update({'MXTPU_METRICS': '1', 'MXTPU_PROFILE': '1',
                'JAX_PLATFORMS': 'cpu'})
    rc = subprocess.call([sys.executable, os.path.abspath(__file__),
                          '--worker', '--outdir', outdir], env=env,
                         timeout=600)
    assert rc == 0, 'serving worker failed (rc %r)' % rc

    trace = os.path.join(outdir, 'serve_trace.json')
    rc = subprocess.call([sys.executable,
                          os.path.join(ROOT, 'tools', 'check_trace.py'),
                          trace])
    assert rc == 0, 'serving trace failed check_trace.py'
    with open(trace) as f:
        doc = json.load(f)
    flushes = [e for e in doc['traceEvents']
               if str(e.get('name', '')).startswith('serving.flush')]
    assert flushes, 'trace recorded no serving.flush spans'
    print('check_serving: trace OK (%d flush spans)' % len(flushes))
    print('check_serving OK')
    return 0


if __name__ == '__main__':
    sys.exit(main())
