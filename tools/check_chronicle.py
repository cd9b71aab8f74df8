#!/usr/bin/env python
"""Chronicle plane smoke — the acceptance gate of the
docs/observability.md "chronicle plane" (hermetic: the parent never
imports jax; children pin their own CPU backend).

One synthetic-JPEG ``Module.fit`` through the full iterator chain under
``MXTPU_CHRONICLE`` + ``MXTPU_PERFWATCH`` + ``MXTPU_IOWATCH``, with an
``io.read:delay`` fault armed MID-RUN (``resilience.set_faults`` — the
arming itself is a typed ``faults/arm`` decision event).  Asserts the
whole story end to end:

1. the journal parses and CAPTURED the ``perf.steps_per_sec`` sag
   (post-injection window mean well under the pre-injection mean);
2. the online detector FIRED: a ``chronicle/anomaly`` decision event
   for ``perf.steps_per_sec`` lands within 3 detector windows of the
   injection;
3. the durable ``flightrec-*-anomaly.json`` postmortem parses and
   embeds the offending window;
4. ``tools/timeline.py`` renders the merged timeline in causal order —
   the ``faults.arm`` injection decision PRECEDES the
   ``chronicle.anomaly`` it caused — honors ``--around``, and its
   ``--strict`` mode accepts the dumps.

A separate off-leg child asserts the zero-surface contract: with
``MXTPU_CHRONICLE`` unset, no sampler thread exists and
``chronicle.query`` returns ``{}``.

Usage: ``python tools/check_chronicle.py [--keep]``.  Exits nonzero on
any failed assertion.  CPU-safe; run by ``tests/test_chronicle.py``
(slow tier) and by hand after touching the chronicle plane.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)

EVERY_MS = 80          # chronicle sampler period for the smoke
DETECT_WINDOW = 32     # detector baseline window (detector.py default)
PRE_S = 2.5            # healthy wall clock before the fault arms
# injected per-BATCH read delay (the io.read fault site fires once per
# record-fetch span): ~4x the healthy step time, so the rolling
# steps_per_sec window sags far past the 4-MAD band within seconds
FAULT_DELAY = 0.12


# ---------------------------------------------------------------------------
# children
# ---------------------------------------------------------------------------

def _child_off(outdir):
    """Zero-surface leg: chronicle knob unset."""
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    import threading
    sys.path.insert(0, _REPO)
    import mxnet_tpu  # noqa: F401 - full package import, knobs read
    from mxnet_tpu import chronicle
    assert not chronicle.enabled(), 'chronicle on without the knob'
    assert chronicle.query('perf.steps_per_sec', 10.0) == {}, \
        'query must return {} when off'
    assert not any(t.name == chronicle.THREAD_NAME
                   for t in threading.enumerate()), \
        'sampler thread exists with the plane off'
    print('RESULT|' + json.dumps({'mode': 'off', 'ok': True}),
          flush=True)


def _child_fit(outdir, batch_size=8, side=24):
    """The injected-stall fit: healthy for PRE_S, then arm the
    io.read delay mid-run and keep fitting while the detector
    watches."""
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import numpy as np
    sys.path.insert(0, _REPO)
    import mxnet_tpu as mx
    from mxnet_tpu import chronicle, recordio, resilience
    from mxnet_tpu.io_record import ImageRecordIter

    assert chronicle.enabled(), 'chronicle knob set but plane off'

    batches, epochs = 40, 5
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

    t0 = time.monotonic()
    state = {'armed_at': None}

    def pace(_param):
        # healthy phase: uniform, quick steps (the baseline the
        # detector learns); once PRE_S elapsed, arm the read delay —
        # the arming emits the faults/arm decision the timeline
        # assertion keys on
        if state['armed_at'] is None:
            if time.monotonic() - t0 >= PRE_S:
                resilience.set_faults('io.read:delay:1:%g'
                                      % FAULT_DELAY)
                state['armed_at'] = time.time()
            else:
                time.sleep(0.025)

    it = ImageRecordIter(path_imgrec=rec_path,
                         data_shape=(3, side, side),
                         batch_size=batch_size,
                         preprocess_threads=2, prefetch_buffer=2)
    it = mx.io.PrefetchingIter(it)

    net = mx.sym.Variable('data')
    net = mx.sym.Flatten(net, name='flat')
    net = mx.sym.FullyConnected(net, num_hidden=10, name='fc')
    net = mx.sym.SoftmaxOutput(net, name='softmax')
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=epochs, optimizer='sgd',
            optimizer_params={'learning_rate': 0.05},
            initializer=mx.init.Uniform(0.05),
            batch_end_callback=pace)
    t_end = time.time()
    resilience.clear_faults()
    # one windowed read through the live query API before shutdown —
    # the Autopilot-facing read path exercised on real data
    post = chronicle.query('perf.steps_per_sec',
                           max(1.0, t_end - (state['armed_at'] or t_end)
                               - 1.0))
    chronicle.stop()       # flush + close the journal for the parent
    print('RESULT|' + json.dumps({
        'mode': 'fit', 't_inj': state['armed_at'], 't_end': t_end,
        'query_post': post,
    }), flush=True)


# ---------------------------------------------------------------------------
# parent
# ---------------------------------------------------------------------------

def _run_child(outdir, mode, extra_env=None, timeout=420):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith('MXTPU_')}
    env.update(extra_env or {})
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         '--run-child', mode, '--outdir', outdir],
        capture_output=True, text=True, timeout=timeout, env=env)
    if out.returncode != 0:
        raise RuntimeError('%s child failed (rc %d):\n%s' %
                           (mode, out.returncode, out.stderr[-3000:]))
    for line in out.stdout.splitlines():
        if line.startswith('RESULT|'):
            return json.loads(line[len('RESULT|'):])
    raise RuntimeError('%s child printed no RESULT line:\n%s'
                       % (mode, out.stdout[-2000:]))


def _read_journal(jdir):
    """(samples, decisions) across every journal segment, oldest
    first.  A torn tail line is tolerated; anything else must parse."""
    samples, decisions, corrupt = [], [], 0
    names = sorted(n for n in os.listdir(jdir)
                   if re.match(r'^journal-(?:\d{6}|active)\.jsonl$', n))
    names.sort(key=lambda n: (n == 'journal-active.jsonl', n))
    for name in names:
        with open(os.path.join(jdir, name)) as f:
            lines = f.readlines()
        for i, line in enumerate(lines):
            if not line.strip():
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                corrupt += 1
                if not (name == 'journal-active.jsonl'
                        and i == len(lines) - 1):
                    raise AssertionError('corrupt non-tail line in %s'
                                         % name)
                continue
            if rec.get('kind') == 'sample':
                samples.append(rec)
            elif rec.get('kind') == 'decision':
                decisions.append(rec.get('ev') or {})
    return samples, decisions


def _timeline(args_list):
    out = subprocess.run(
        [sys.executable, os.path.join(_HERE, 'timeline.py')]
        + args_list, capture_output=True, text=True, timeout=120)
    return out.returncode, out.stdout + out.stderr


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--keep', action='store_true',
                    help='keep the scratch dir (prints its path)')
    ap.add_argument('--run-child', default=None, help=argparse.SUPPRESS)
    ap.add_argument('--outdir', default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.run_child == 'off':
        _child_off(args.outdir)
        return 0
    if args.run_child == 'fit':
        _child_fit(args.outdir)
        return 0

    assert 'jax' not in sys.modules, \
        'check_chronicle parent must stay jax-free'
    outdir = tempfile.mkdtemp(prefix='mxtpu_check_chronicle_')
    jdir = os.path.join(outdir, 'journal')
    failures = []

    def check(cond, msg):
        print('%s %s' % ('OK  ' if cond else 'FAIL', msg))
        if not cond:
            failures.append(msg)

    try:
        # leg 0: zero surface off
        off = _run_child(outdir, 'off')
        check(off.get('ok') is True,
              'off-leg: no thread, no surface, query == {}')

        # leg 1: the injected-stall fit
        fit = _run_child(outdir, 'fit', extra_env={
            'MXTPU_CHRONICLE': jdir,
            'MXTPU_CHRONICLE_EVERY_MS': str(EVERY_MS),
            'MXTPU_PERFWATCH': '1',
            # the peak table holds real chips only: a nominal figure
            # keeps perf.mfu defined on the CPU backend
            'MXTPU_PEAK_FLOPS': os.environ.get('MXTPU_PEAK_FLOPS',
                                               '2e11'),
            'MXTPU_IOWATCH': '1',
        }, timeout=600)
        t_inj = fit.get('t_inj')
        check(isinstance(t_inj, (int, float)),
              'fault armed mid-run (t_inj recorded)')
        samples, decisions = _read_journal(jdir)
        check(len(samples) >= 20,
              'journal holds >= 20 samples (got %d)' % len(samples))

        # the journal CAPTURED the sag: windowed means around t_inj
        def sps_mean(lo, hi):
            vals = [s['gauges']['perf.steps_per_sec'] for s in samples
                    if lo <= s['t'] <= hi
                    and 'perf.steps_per_sec' in s['gauges']]
            return (sum(vals) / len(vals)) if vals else None

        pre = sps_mean(t_inj - 2.0, t_inj)
        post = sps_mean(t_inj + 3.0, fit['t_end'])
        check(pre is not None and post is not None,
              'steps_per_sec journaled both sides of the injection '
              '(pre=%s post=%s)' % (pre, post))
        if pre and post:
            check(post < 0.7 * pre,
                  'journal captured the sag (%.2f -> %.2f steps/s)'
                  % (pre, post))

        # the detector FIRED, within 3 windows of the injection
        anomalies = [d for d in decisions
                     if d.get('subsystem') == 'chronicle'
                     and d.get('action') == 'anomaly'
                     and d.get('series') == 'perf.steps_per_sec']
        check(bool(anomalies), 'chronicle/anomaly decision for '
                               'perf.steps_per_sec journaled')
        arms = [d for d in decisions
                if d.get('subsystem') == 'faults'
                and d.get('action') == 'arm']
        check(bool(arms), 'faults/arm injection decision journaled')
        if anomalies:
            window_s = DETECT_WINDOW * EVERY_MS / 1000.0
            lag = anomalies[0]['t'] - t_inj
            check(0 < lag <= 3 * window_s,
                  'detector fired %.2fs after injection '
                  '(<= 3 windows = %.2fs)' % (lag, 3 * window_s))

        # the durable postmortem parses and embeds the window
        pms = [n for n in os.listdir(jdir)
               if n.startswith('flightrec-') and
               n.endswith('-anomaly.json')]
        check(bool(pms), 'flightrec-*-anomaly.json postmortem written')
        # other series (goodput.fraction legitimately sags too) may
        # write their own postmortems — find the steps_per_sec one
        target = None
        for name in sorted(pms):
            with open(os.path.join(jdir, name)) as f:
                doc = json.load(f)
            if (doc.get('anomaly') or {}).get('series') == \
                    'perf.steps_per_sec':
                target = doc
                break
        anom = (target or {}).get('anomaly') or {}
        check(target is not None
              and len(anom.get('window') or []) >= 2,
              'steps_per_sec postmortem embeds the offending window '
              '(%d samples)' % len(anom.get('window') or []))

        # the merged timeline: causal order + --around + --strict
        rc, txt = _timeline([jdir, '--strict'])
        check(rc == 0, 'timeline --strict accepts the dumps (rc %d)'
              % rc)
        lines = [ln for ln in txt.splitlines()
                 if 'faults.arm' in ln
                 or ('chronicle.anomaly' in ln
                     and 'perf.steps_per_sec' in ln)]
        arm_idx = next((i for i, ln in enumerate(lines)
                        if 'faults.arm' in ln), None)
        anom_idx = next((i for i, ln in enumerate(lines)
                         if 'chronicle.anomaly' in ln), None)
        check(arm_idx is not None and anom_idx is not None
              and arm_idx < anom_idx,
              'timeline orders faults.arm before chronicle.anomaly')
        if isinstance(t_inj, (int, float)):
            rc2, txt2 = _timeline([jdir, '--around', '%f' % t_inj,
                                   '--window', '1.0'])
            check(rc2 == 0 and 'faults.arm' in txt2,
                  'timeline --around the injection names faults.arm')
    finally:
        if args.keep:
            print('scratch kept: %s' % outdir)
        else:
            shutil.rmtree(outdir, ignore_errors=True)

    if failures:
        print('\n%d check(s) FAILED' % len(failures), file=sys.stderr)
        return 1
    print('\nchronicle smoke OK')
    return 0


if __name__ == '__main__':
    sys.exit(main())
