#!/usr/bin/env python
"""Perf-regression gate: compare two bench result files leg by leg and
exit nonzero when the current round regressed past tolerance.

Usage::

    python tools/check_perf.py BASELINE.json CURRENT.json \
        [--tol 0.10] [--leg-tol LEG=FRAC ...] [--require-all]

Accepted file shapes (auto-detected, mixable):

- ``bench_state.json`` / ``BENCH_metrics``-adjacent per-leg form:
  ``{"resnet50_train": {"value": 2303.1, "mfu": 0.61, ...}, ...}``
  (bare-number legacy values tolerated);
- the driver's one-line primary form:
  ``{"metric": "resnet50_train_imgs_per_sec_per_chip", "value": ...}``
  (treated as a single leg named by ``metric``).

Per-leg semantics: throughput-like ``value``s and ``mfu`` are
higher-is-better (regression = current < baseline * (1 - tol));
``warmup_secs`` and ``*_pct``/``*_secs``/``*_ms`` overhead legs are
lower-is-better (regression = current > baseline * (1 + tol) + abs
slack, so a 1.5% -> 1.6% overhead wiggle does not page anyone); the
communication-plane fields (``comm_fraction``, ``comm_bytes_per_step``
— persisted by the multichip leg under MXTPU_COMMWATCH) are
lower-is-better too, with a small absolute slack on the [0, 1]
fraction; the ``goodput_fraction`` leg (the iowatch plane's hermetic
bench leg) is gated HIGHER-is-better with a purely absolute 0.02
slack; the ``recovery_time_secs`` leg (elastic repair latency,
``tools/check_elastic.py --bench``) is lower-is-better with 50%
relative + 2s absolute slack — it is dominated by fixed detection
timeouts plus host jitter.  The ``replica_recovery_secs`` leg (the
serving supervisor's quarantine->replacement repair, off
``tools/check_fleet.py --bench``'s chaos leg) gets the same
lower-is-better 50% + 2s treatment for the same reason: the figure is
mostly the supervisor's detection interval plus scheduler jitter.
Legs present only in the baseline are warnings unless
``--require-all``.

Run by ``tests/test_perfwatch.py`` as a self-comparison smoke so the
gate itself stays exercised under tier-1.
"""
from __future__ import annotations

import argparse
import json
import sys

# default relative tolerance per compared field; the gate is meant to
# catch real cliffs, not timer noise
DEFAULT_TOL = 0.10
FIELD_TOL = {'warmup_secs': 0.25}
# absolute slack added on the lower-is-better side (units of the
# field).  Kept small: overhead legs sit near 1-2 in their unit, so a
# generous slack would wave through exactly the multiples the gate
# exists to catch (0.5pp covers a 1.5% -> 1.6% wiggle, not a 2x blowup).
# comm_fraction lives in [0, 1]: 0.02 absolute covers roofline-table
# jitter, while a step that went from compute-bound to comm-bound
# (say 0.1 -> 0.4) still trips the gate.  goodput_fraction is its
# HIGHER-is-better mirror (the iowatch plane's bench leg): same 0.02
# absolute slack, relative tolerance zeroed via LEG_TOL so the bound
# is purely absolute — a 0.95 baseline trips below 0.93, which a
# 10%-relative bound (0.855) would wave through
ABS_SLACK = {'warmup_secs': 0.5, 'pct': 0.5, 'ms': 0.5,
             'comm_fraction': 0.02, 'goodput_fraction': 0.02,
             # the elastic repair leg is dominated by fixed timeouts
             # (dead-timeout + MXTPU_ELASTIC_WAIT) plus scheduler
             # jitter on an oversubscribed host: 2s absolute covers
             # the jitter while a detect->repair path that doubled
             # still trips the 50% relative bound below
             'recovery_time_secs': 2.0,
             # the serving chaos leg's repair figure is mostly the
             # supervisor poll interval + host jitter, like the
             # elastic leg above
             'replica_recovery_secs': 2.0}

# every other compared field (value, mfu, pct_of_raw_step) is
# higher-is-better.  The communication-plane fields are lower-is-better:
# a leg whose comm_fraction / comm_bytes_per_step GREW is paying the
# interconnect more for the same work (a lost overlap, a new collective,
# a degraded sharding) even if throughput noise hides it this round
LOWER_BETTER_FIELDS = ('warmup_secs', 'p99_ms', 'p50_ms',
                       'comm_fraction', 'comm_bytes_per_step')

# built-in per-leg tolerances (the --leg-tol CLI overrides these):
# multichip_fit_ips measures 8-way-sharded throughput on VIRTUAL CPU
# devices — all eight "chips" contend for the same host cores, so
# run-to-run noise is far above the accelerator legs' and the default
# 10% would page on scheduler jitter, not regressions
# serve_fleet_qps rides the same virtual-device contention as the
# multichip leg (replica workers + closed-loop clients all share the
# host cores), so it gets the same generous relative bound
LEG_TOL = {'multichip_fit_ips': 0.30, 'goodput_fraction': 0.0,
           'recovery_time_secs': 0.5, 'serve_fleet_qps': 0.30,
           'replica_recovery_secs': 0.5}


def _lower_better_leg(leg):
    """Legs whose primary value is an overhead/latency (smaller wins)."""
    return leg.endswith('_pct') or leg.endswith('_secs') or \
        leg.endswith('_ms')


def load_legs(path):
    """Normalize either accepted file shape into {leg: {field: num}}."""
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict):
        raise ValueError('%s: not a JSON object' % path)
    if 'metric' in doc and 'value' in doc:
        return {str(doc['metric']): {'value': float(doc['value'])}}
    legs = {}
    for leg, entry in doc.items():
        if isinstance(entry, (int, float)):
            legs[str(leg)] = {'value': float(entry)}
        elif isinstance(entry, dict) and 'value' in entry:
            fields = {'value': float(entry['value'])}
            for k in ('mfu', 'warmup_secs', 'pct_of_raw_step',
                      'p99_ms', 'p50_ms', 'comm_fraction',
                      'comm_bytes_per_step', 'scaling'):
                v = entry.get(k)
                if isinstance(v, (int, float)):
                    fields[k] = float(v)
            legs[str(leg)] = fields
    return legs


def _abs_slack(leg, field):
    if field in ABS_SLACK:
        return ABS_SLACK[field]
    if field == 'value' and leg in ABS_SLACK:
        return ABS_SLACK[leg]
    if leg.endswith('_pct'):
        return ABS_SLACK['pct']
    if field.endswith('_ms') or leg.endswith('_ms'):
        return ABS_SLACK['ms']
    return 0.0


def compare(base_legs, cur_legs, tol=DEFAULT_TOL, leg_tol=None,
            require_all=False):
    """Return (rows, regressions, missing): rows are
    ``(leg, field, baseline, current, status)`` with status one of
    'ok'/'REGRESSED'/'improved'/'missing'."""
    leg_tol = dict(LEG_TOL, **(leg_tol or {}))
    rows, regressions, missing = [], [], []
    for leg in sorted(base_legs):
        if leg not in cur_legs:
            missing.append(leg)
            rows.append((leg, 'value', base_legs[leg].get('value'),
                         None, 'missing'))
            continue
        base, cur = base_legs[leg], cur_legs[leg]
        for field in sorted(base):
            if field not in cur:
                continue
            b, c = base[field], cur[field]
            t = leg_tol.get(leg, FIELD_TOL.get(field, tol))
            lower_better = field in LOWER_BETTER_FIELDS or \
                (field == 'value' and _lower_better_leg(leg))
            if lower_better:
                bad = c > b * (1.0 + t) + _abs_slack(leg, field)
                better = c < b
            else:
                # abs slack applies symmetrically: goodput_fraction's
                # higher-is-better bound is b - 0.02 (t is 0 for it)
                bad = c < b * (1.0 - t) - _abs_slack(leg, field)
                better = c > b
            status = 'REGRESSED' if bad else \
                ('improved' if better else 'ok')
            if bad:
                regressions.append((leg, field, b, c))
            rows.append((leg, field, b, c, status))
    if require_all:
        for leg in missing:
            regressions.append((leg, 'value',
                                base_legs[leg].get('value'), None))
    return rows, regressions, missing


def main(argv=None):
    ap = argparse.ArgumentParser(
        description='compare two bench result files; nonzero exit on '
                    'regression')
    ap.add_argument('baseline')
    ap.add_argument('current')
    ap.add_argument('--tol', type=float, default=DEFAULT_TOL,
                    help='default relative tolerance (fraction, '
                         'default %(default)s)')
    ap.add_argument('--leg-tol', action='append', default=[],
                    metavar='LEG=FRAC',
                    help='per-leg tolerance override (repeatable)')
    ap.add_argument('--require-all', action='store_true',
                    help='a leg present in baseline but absent in '
                         'current is a regression, not a warning')
    args = ap.parse_args(argv)
    leg_tol = {}
    for spec in args.leg_tol:
        leg, _, frac = spec.partition('=')
        try:
            leg_tol[leg] = float(frac)
        except ValueError:
            ap.error('bad --leg-tol %r' % spec)
    try:
        base_legs = load_legs(args.baseline)
        cur_legs = load_legs(args.current)
    except (OSError, ValueError) as e:
        print('check_perf: %s' % e, file=sys.stderr)
        return 2
    rows, regressions, missing = compare(base_legs, cur_legs,
                                         tol=args.tol, leg_tol=leg_tol,
                                         require_all=args.require_all)
    for leg, field, b, c, status in rows:
        print('%-34s %-16s %12s -> %-12s %s'
              % (leg, field,
                 '%.4g' % b if b is not None else '-',
                 '%.4g' % c if c is not None else '-', status))
    for leg in missing:
        print('check_perf: WARNING leg %r missing from current%s'
              % (leg, ' (counted as regression)' if args.require_all
                 else ''), file=sys.stderr)
    if regressions:
        for leg, field, b, c in regressions:
            print('check_perf: REGRESSION %s.%s %s -> %s'
                  % (leg, field, b, c), file=sys.stderr)
        return 1
    print('check_perf: OK (%d legs compared, %d rows)'
          % (len([r for r in rows if r[4] != 'missing']), len(rows)))
    return 0


if __name__ == '__main__':
    sys.exit(main())
