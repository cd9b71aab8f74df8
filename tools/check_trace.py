#!/usr/bin/env python
"""Validate Chrome-trace JSON files dumped by mxnet_tpu.instrument /
profiler (the ``src/engine/profiler.cc`` dump format, grown thread
metadata).

Usage: ``python tools/check_trace.py TRACE.json [TRACE2.json ...]``

Exits nonzero when any file is malformed: not JSON, no ``traceEvents``
list, or any event missing the fields Perfetto/chrome://tracing need
(``name``/``ph``/``pid`` everywhere; ``ts``/``tid`` on data events;
numeric non-negative ``dur`` on complete events).  Performance-plane
events (``perf.step`` sampled-step spans, ``perf.fit_step`` iteration
roots, ``perf.phase.*`` phase attribution) are additionally
structure-checked: a ``perf.step`` span with no phase child inside its
interval on its own thread is rejected — a merged multi-rank trace
where the breakdown was lost is not honest — and so is a
``perf.fit_step`` root that a phase on its thread sticks out of, or
that overlaps the next root.
Request-attribution spans (``serve.request``/``serve.req.*``/
``serve.flush`` from MXTPU_SERVEWATCH) are ledger-checked: a request's
six exclusive buckets must sum to its e2e span within tolerance, and
the on-flush buckets must nest inside the flush span they name.

Merged multi-rank dumps (``tools/merge_traces.py`` marks each aligned
lane with ``clock_sync`` metadata) are additionally CLOCK-checked: the
anchor spans the lanes were aligned on must coincide within
``ALIGN_TOL_US`` across ranks — offset-inconsistent lanes mean the
merge's simultaneity claim is false (clock skew read as straggling),
so the dump is rejected.
Run by ``tests/test_instrument.py`` / ``tests/test_perfwatch.py`` /
``tests/test_commwatch.py`` so the validator itself stays exercised
under tier-1.
"""
from __future__ import annotations

import bisect
import json
import sys

# how far apart two rank lanes' shared-anchor instants may sit in a
# merged dump before the lanes count as offset-inconsistent.  Barrier
# release skew is network RTT (sub-ms on a rack); 250ms only catches
# genuinely unaligned clocks, not jitter.
ALIGN_TOL_US = 250000

# phases that mark a data event on the timeline (complete, duration
# begin/end, instant, counter); 'M' is metadata and carries no ts/tid
_DATA_PHASES = ('X', 'B', 'E', 'i', 'I', 'C')


def validate_events(events):
    """Return a list of 'event #i: problem' strings (empty = valid)."""
    errors = []
    if not isinstance(events, list):
        return ['traceEvents is not a list']
    for i, e in enumerate(events):
        def err(msg):
            errors.append('event #%d: %s (%r)' % (i, msg, e))
        if not isinstance(e, dict):
            err('not an object')
            continue
        ph = e.get('ph')
        if not isinstance(e.get('name'), str) or not e['name']:
            err('missing/empty name')
        if not isinstance(ph, str) or not ph:
            err('missing ph')
            continue
        if 'pid' not in e:
            err('missing pid')
        if ph == 'M':
            continue
        if ph not in _DATA_PHASES:
            err('unknown phase %r' % ph)
            continue
        if 'tid' not in e:
            err('missing tid')
        if not isinstance(e.get('ts'), (int, float)):
            err('missing/non-numeric ts')
        if ph == 'X':
            dur = e.get('dur')
            if not isinstance(dur, (int, float)) or dur < 0:
                err('complete event needs non-negative numeric dur')
        if isinstance(e.get('name'), str) and \
                (e['name'] in ('perf.step', 'perf.fit_step') or
                 e['name'].startswith('perf.phase.')) and ph != 'X':
            err('performance-plane event must be a complete (X) span')
    errors.extend(_validate_perf_steps(events))
    errors.extend(_validate_request_spans(events))
    errors.extend(_validate_decision_events(events))
    errors.extend(_validate_rank_alignment(events))
    return errors


def anchor_end(events, anchor, pid=None):
    """END ts (us) of the FIRST complete span named ``anchor``
    (restricted to ``pid``'s lane when given); None when absent.  The
    end, not the start: ranks ENTER a barrier at different times —
    that spread is the thing being measured — they LEAVE it together.
    Shared with ``tools/merge_traces.py`` (the aligner), so the shift
    rule and the validator's consistency rule can never drift apart."""
    best = None
    for e in events:
        if not isinstance(e, dict) or e.get('ph') != 'X' or \
                e.get('name') != anchor:
            continue
        if pid is not None and e.get('pid') != pid:
            continue
        ts, dur = e.get('ts'), e.get('dur')
        if not isinstance(ts, (int, float)) or \
                not isinstance(dur, (int, float)):
            continue
        if best is None or ts < best[0]:
            best = (ts, ts + dur)
    return best[1] if best is not None else None


def _validate_rank_alignment(events):
    """Merged multi-rank dumps carry one ``clock_sync`` metadata event
    per ALIGNED lane (merge_traces.py).  Every pair of aligned lanes
    must agree on the shared anchor instant within ALIGN_TOL_US —
    otherwise the merged timeline's cross-rank ordering is a clock
    artifact and the dump is rejected."""
    synced = {}           # pid -> anchor name
    for e in events:
        if isinstance(e, dict) and e.get('ph') == 'M' and \
                e.get('name') == 'clock_sync':
            args = e.get('args') or {}
            if args.get('aligned') and isinstance(args.get('anchor'),
                                                  str):
                synced[e.get('pid')] = args['anchor']
    if len(synced) < 2:
        return []
    ends = {}
    for pid, anchor in synced.items():
        end = anchor_end(events, anchor, pid=pid)
        if end is not None:
            ends[pid] = end
    if len(ends) < 2:
        return []
    lo_pid = min(ends, key=ends.get)
    hi_pid = max(ends, key=ends.get)
    spread = ends[hi_pid] - ends[lo_pid]
    if spread > ALIGN_TOL_US:
        return ['rank lanes offset-inconsistent: anchor spans of pid %s '
                'and pid %s are %.0fus apart (> %dus) — the merged '
                'timeline\'s cross-rank ordering is a clock artifact'
                % (lo_pid, hi_pid, spread, ALIGN_TOL_US)]
    return []


# a Chrome span's ts and dur are each floored to a microsecond from one
# nanosecond clock, so a child that ends with its parent can read up to
# a microsecond past it at either end
_NEST_TOL_US = 2


def _validate_perf_steps(events):
    """Every ``perf.step`` sampled-step span must contain at least one
    ``perf.phase.*`` child on the same pid/tid inside its interval —
    the step-time breakdown the span exists to carry.

    Every ``perf.fit_step`` root (one iteration of the fit loop) must
    contain the ``perf.phase.*`` spans that meet it on its own pid/tid:
    a phase that sticks out of its root, or two roots that overlap,
    is a span tree whose self times mean nothing."""
    steps = []
    phases = []
    roots = []
    for e in events:
        if not isinstance(e, dict) or e.get('ph') != 'X':
            continue
        name = e.get('name')
        ts, dur = e.get('ts'), e.get('dur')
        if not isinstance(name, str) or \
                not isinstance(ts, (int, float)) or \
                not isinstance(dur, (int, float)):
            continue
        key = (e.get('pid'), e.get('tid'))
        if name == 'perf.step':
            steps.append((key, ts, ts + dur))
        elif name == 'perf.fit_step':
            roots.append((key, ts, ts + dur))
        elif name.startswith('perf.phase.'):
            phases.append((key, ts, ts + dur, name))
    errors = []
    by_thread = {}
    for key, t0, t1 in roots:
        by_thread.setdefault(key, []).append((t0, t1))
    for key, spans in by_thread.items():
        spans.sort()
        for (t0, t1), (u0, _) in zip(spans, spans[1:]):
            if u0 < t1 - _NEST_TOL_US:
                errors.append('perf.fit_step roots at ts=%s and ts=%s '
                              '(pid/tid %s) overlap' % (t0, u0, key))
    for key, p0, p1, name in phases:
        spans = by_thread.get(key, ())
        # the root that starts last at or before the phase, and the next
        at = bisect.bisect_right(spans, (p0 + _NEST_TOL_US, float('inf')))
        for t0, t1 in spans[max(at - 1, 0):at + 1]:
            if p0 < t1 - _NEST_TOL_US and p1 > t0 + _NEST_TOL_US and \
                    (p0 < t0 - _NEST_TOL_US or p1 > t1 + _NEST_TOL_US):
                errors.append('%s span [%s, %s] sticks out of the '
                              'perf.fit_step root [%s, %s] it meets '
                              '(pid/tid %s)' % (name, p0, p1, t0, t1, key))
    for key, t0, t1 in steps:
        if not any(pk == key and p0 >= t0 and p1 <= t1
                   for pk, p0, p1, _ in phases):
            errors.append('perf.step span at ts=%s (pid/tid %s) has no '
                          'perf.phase.* child inside its interval'
                          % (t0, key))
    return errors


def _validate_decision_events(events):
    """Chronicle decision instants (``instrument.decision`` under
    profiling: ``decision.<subsystem>.<action>`` with
    ``cat='decision'``) carry a typed payload and a per-subsystem lane
    invariant — ``seq`` monotonic and ``ts`` non-decreasing with it —
    so merged timelines cannot silently interleave corrupt events.
    Untyped args or a lane whose seq/time order disagree reject the
    dump."""
    lanes = {}            # (pid, subsystem) -> [(seq, ts)]
    errors = []
    for i, e in enumerate(events):
        if not isinstance(e, dict):
            continue
        name = e.get('name')
        is_decision = e.get('cat') == 'decision' or \
            (isinstance(name, str) and name.startswith('decision.'))
        if not is_decision:
            continue
        args = e.get('args') or {}
        sub, act, seq = args.get('subsystem'), args.get('action'), \
            args.get('seq')
        if not isinstance(sub, str) or not sub or \
                not isinstance(act, str) or not act or \
                not isinstance(seq, int):
            errors.append('event #%d: decision event without typed '
                          'subsystem/action/seq args (%r)' % (i, e))
            continue
        ts = e.get('ts')
        if isinstance(ts, (int, float)):
            lanes.setdefault((e.get('pid'), sub), []).append((seq, ts))
    for (pid, sub), evs in sorted(lanes.items(),
                                  key=lambda kv: (str(kv[0][0]),
                                                  kv[0][1])):
        seqs = [s for s, _ in evs]
        if len(set(seqs)) != len(seqs):
            # a merged dump holding several runs' lanes (seq restarts
            # per process) has no cross-run order invariant
            continue
        evs.sort()
        for (s0, t0), (s1, t1) in zip(evs, evs[1:]):
            if t1 < t0:
                errors.append('decision lane pid=%s %r: seq %d '
                              '(ts=%s) precedes seq %d (ts=%s) — seq '
                              'and time order disagree'
                              % (pid, sub, s1, t1, s0, t0))
    return errors


# the request-attribution plane's exclusive buckets, chain order —
# mirrors mxnet_tpu/serving/servewatch.py BUCKETS
_REQ_BUCKETS = ('admission_wait', 'lane_wait', 'coalesce_wait', 'pad',
                'execute', 'slice_deliver')

# buckets that happen ON the flush (worker thread, replica held) —
# must nest inside the request's serve.flush span.  The waits happen
# before the batch is taken and legitimately start outside it.
_ON_FLUSH_BUCKETS = ('pad', 'execute', 'slice_deliver')

# integer-us rounding slack per nesting comparison
_REQ_NEST_SLACK_US = 1


def _validate_request_spans(events):
    """Request-attribution spans (servewatch, MXTPU_SERVEWATCH) carry
    an EXACTNESS claim: the six exclusive ``serve.req.<bucket>`` spans
    of a request must telescope to its ``serve.request`` e2e span, and
    the on-flush buckets (pad/execute/slice_deliver) must nest inside
    the ``serve.flush`` span the request's ``args.flush`` names on the
    same lane.  A dump violating either is attributing time it did not
    measure, so it is rejected."""
    flushes = {}          # flush id -> (pid, tid, ts, end)
    reqs = {}             # req id -> {'e2e': (ts,end), 'flush': id,
                          #            'key': (pid,tid),
                          #            'buckets': {name: (ts,end)}}
    for e in events:
        if not isinstance(e, dict) or e.get('ph') != 'X':
            continue
        name = e.get('name')
        ts, dur = e.get('ts'), e.get('dur')
        if not isinstance(name, str) or \
                not isinstance(ts, (int, float)) or \
                not isinstance(dur, (int, float)):
            continue
        args = e.get('args') or {}
        key = (e.get('pid'), e.get('tid'))
        if name == 'serve.flush' and args.get('flush') is not None:
            flushes[str(args['flush'])] = (key, ts, ts + dur)
        elif name == 'serve.request' and args.get('req') is not None:
            r = reqs.setdefault(str(args['req']), {'buckets': {}})
            r['e2e'] = (ts, ts + dur)
            r['flush'] = args.get('flush')
            r['key'] = key
        elif name.startswith('serve.req.') and \
                args.get('req') is not None:
            bucket = name[len('serve.req.'):]
            r = reqs.setdefault(str(args['req']), {'buckets': {}})
            r['buckets'][bucket] = (ts, ts + dur)
    errors = []
    for rid in sorted(reqs):
        r = reqs[rid]
        if 'e2e' not in r:
            errors.append('request %s: serve.req.* spans without a '
                          'serve.request e2e span' % rid)
            continue
        missing = [b for b in _REQ_BUCKETS if b not in r['buckets']]
        if missing:
            errors.append('request %s: bucket span(s) missing: %s'
                          % (rid, ', '.join(missing)))
            continue
        t0, t1 = r['e2e']
        e2e = t1 - t0
        total = sum(b1 - b0 for b0, b1 in r['buckets'].values())
        # integer-us spans telescope exactly; allow rounding +
        # float-tolerance headroom only
        tol = max(4, 0.01 * e2e)
        if abs(total - e2e) > tol:
            errors.append('request %s: exclusive buckets sum to '
                          '%.0fus but e2e span is %.0fus (>%.0fus '
                          'off) — the attribution ledger is broken'
                          % (rid, total, e2e, tol))
        fid = r.get('flush')
        if fid is None or str(fid) not in flushes:
            # a dump sliced after the request spans but before the
            # flush close would orphan the chain; only enforce
            # nesting when the named flush span is present
            continue
        fkey, f0, f1 = flushes[str(fid)]
        for b in _ON_FLUSH_BUCKETS:
            b0, b1 = r['buckets'][b]
            if r['key'] != fkey:
                errors.append('request %s: span lane %s does not '
                              'match its flush %s lane %s'
                              % (rid, r['key'], fid, fkey))
                break
            if b0 < f0 - _REQ_NEST_SLACK_US or \
                    b1 > f1 + _REQ_NEST_SLACK_US:
                errors.append('request %s: serve.req.%s span '
                              '[%.0f, %.0f] falls outside its flush '
                              '%s span [%.0f, %.0f]'
                              % (rid, b, b0, b1, fid, f0, f1))
    return errors


def validate_file(path):
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return ['cannot load %s: %s' % (path, e)]
    if isinstance(doc, list):        # bare-array trace form is legal
        return validate_events(doc)
    if not isinstance(doc, dict) or 'traceEvents' not in doc:
        return ['%s: no traceEvents key' % path]
    return validate_events(doc['traceEvents'])


def main(argv):
    if len(argv) < 2:
        print(__doc__.strip(), file=sys.stderr)
        return 2
    rc = 0
    for path in argv[1:]:
        errors = validate_file(path)
        if errors:
            rc = 1
            for msg in errors[:20]:
                print('%s: %s' % (path, msg), file=sys.stderr)
            extra = len(errors) - 20
            if extra > 0:
                print('%s: ... %d more' % (path, extra), file=sys.stderr)
        else:
            print('%s: OK' % path)
    return rc


if __name__ == '__main__':
    sys.exit(main(sys.argv))
