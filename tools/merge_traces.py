#!/usr/bin/env python
"""Merge rank-tagged Chrome traces into ONE cluster timeline.

Each worker of a distributed run dumps its own trace
(``instrument.dump_trace``) with its OS pid.  This tool rewrites every
event's ``pid`` to the worker's RANK and concatenates the files, so the
merged timeline shows one process lane per rank in Perfetto /
``chrome://tracing`` — the cross-worker timeline aggregation of the
training-health plane (docs/observability.md).

**Clock alignment**: per-rank timestamps come from each process's own
clock — across hosts (or after an NTP step) the lanes land offset, and
a "straggler" in the merged view may be nothing but clock skew.  Ranks
are therefore aligned on a SHARED ANCHOR before merging: the end of the
first ``--anchor`` span (default ``kvstore.barrier`` — every rank
leaves a barrier at the same real instant, so its end is a cluster-wide
simultaneity marker).  Each lane is shifted so its anchor coincides
with the cluster median; the applied offset is recorded in a
``clock_sync`` metadata event per lane, and ``tools/check_trace.py``
REJECTS merged dumps whose aligned lanes disagree past tolerance
(offset-inconsistent lanes make cross-rank reading dishonest).  Ranks
without the anchor event merge unshifted (warned, ``aligned: false``).

Usage::

    python tools/merge_traces.py -o merged.json rank0.json rank1.json ...
    python tools/merge_traces.py -o merged.json --ranks 0,3 a.json b.json
    python tools/merge_traces.py -o merged.json \\
        --anchor perf.setup.warm_start --no-align r0.json r1.json

**Replica lanes**: serving events (``cat: 'serving'`` — flush spans
and the MXTPU_SERVEWATCH request-attribution chains) carry their
``model``/``replica`` in ``args``.  By default they are RELANED onto a
synthetic tid per (model, replica) with a ``serve <model>/r<N>``
thread name, so a merged fleet dump renders one lane per replica with
request spans nested inside their flush — instead of every worker
thread of every file collapsing into whatever raw tids collided.
``--no-relane`` keeps raw worker tids.

Ranks come from ``--ranks`` (one per input, in order), else from a
``rank<N>`` substring in each filename, else from the input position.
The output carries ``process_name`` metadata (``rank N``) per lane,
preserves per-file ``thread_name`` metadata under the rewritten pid,
and is validated with ``tools/check_trace.py`` before the tool exits 0.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import zlib

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
import check_trace  # noqa: E402  (tools/check_trace.py)

_RANK_RE = re.compile(r'rank[-_]?(\d+)')

DEFAULT_ANCHOR = 'kvstore.barrier'

# synthetic-tid floor for relaned serving lanes — far above OS thread
# ids so a replica lane can never collide with a real thread's tid
SERVE_LANE_BASE = 1 << 20


def _serve_lane(e):
    """(tid, thread-name) of the replica lane a serving event belongs
    on, or None.  Qualifies: ``cat == 'serving'`` with non-None
    ``model`` AND ``replica`` in args — servewatch deliberately stamps
    both on every flush/request/bucket span so whole request chains
    relane TOGETHER with their flush."""
    if e.get('cat') != 'serving':
        return None
    args = e.get('args') or {}
    model, rep = args.get('model'), args.get('replica')
    if model is None or rep is None:
        return None
    label = 'serve %s/r%s' % (model, rep)
    tid = SERVE_LANE_BASE + (zlib.crc32(label.encode()) & 0xFFFF)
    return tid, label


def _infer_rank(path, position):
    m = _RANK_RE.search(os.path.basename(path))
    return int(m.group(1)) if m else position


def _load_events(path):
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, list):          # bare-array trace form is legal
        return doc
    return doc.get('traceEvents', [])


def _anchor_ts(events, anchor):
    """END timestamp (us) of one rank's shared-anchor span —
    ``check_trace.anchor_end``, the SAME selection rule the merged-dump
    validator measures consistency with (a private copy here could
    drift and make the validator reject correctly aligned dumps)."""
    return check_trace.anchor_end(events, anchor)


def _median(vals):
    vals = sorted(vals)
    mid = len(vals) // 2
    return vals[mid] if len(vals) % 2 else \
        0.5 * (vals[mid - 1] + vals[mid])


def merge(paths, ranks=None, anchor=DEFAULT_ANCHOR, align=True,
          relane=True):
    """Merge trace files into one Chrome-trace document dict.  ``ranks``
    is an optional list parallel to ``paths``; events keep their tid
    (threads stay distinct lanes inside each rank's process group).
    With ``align`` (default), rank clocks are shifted onto the shared
    ``anchor`` span's end before merging.  With ``relane`` (default),
    serving events move onto one synthetic lane per (model, replica)."""
    if ranks is not None and len(ranks) != len(paths):
        raise ValueError('--ranks needs exactly one rank per input '
                         '(%d ranks for %d files)'
                         % (len(ranks), len(paths)))
    per_rank = []
    for i, path in enumerate(paths):
        rank = ranks[i] if ranks is not None else _infer_rank(path, i)
        events = _load_events(path)
        per_rank.append((rank, path, events,
                         _anchor_ts(events, anchor) if align else None))

    anchors = [a for _, _, _, a in per_rank if a is not None]
    ref = _median(anchors) if len(anchors) >= 2 else None

    data, meta = [], []
    for rank, path, events, a in per_rank:
        offset = (ref - a) if (ref is not None and a is not None) else 0
        if align:
            if ref is not None and a is None:
                print('merge_traces: WARNING %s (rank %d) has no %r '
                      'anchor span — lane merged UNALIGNED'
                      % (path, rank, anchor), file=sys.stderr)
            meta.append({'name': 'clock_sync', 'ph': 'M', 'pid': rank,
                         'args': {'anchor': anchor,
                                  'offset_us': offset,
                                  'aligned': bool(ref is not None
                                                  and a is not None)}})
        meta.append({'name': 'process_name', 'ph': 'M', 'pid': rank,
                     'args': {'name': 'rank %d' % rank}})
        lanes = {}             # synthetic tid -> thread-name label
        for e in events:
            if not isinstance(e, dict):
                continue
            e = dict(e)
            e['pid'] = rank
            if e.get('ph') == 'M':
                # per-file process_name is replaced by the rank lane
                # label above; thread_name metadata survives rewritten
                if e.get('name') == 'process_name':
                    continue
                meta.append(e)
            else:
                if relane:
                    lane = _serve_lane(e)
                    if lane is not None:
                        e['tid'] = lane[0]
                        lanes[lane[0]] = lane[1]
                if offset and isinstance(e.get('ts'), (int, float)):
                    e['ts'] = e['ts'] + offset
                data.append(e)
        for tid in sorted(lanes):
            meta.append({'name': 'thread_name', 'ph': 'M', 'pid': rank,
                         'tid': tid, 'args': {'name': lanes[tid]}})
    data.sort(key=lambda e: e.get('ts', 0))
    return {'traceEvents': data + meta, 'displayTimeUnit': 'ms'}


def main(argv=None):
    ap = argparse.ArgumentParser(
        description='merge rank-tagged Chrome traces (pid=rank), '
                    'aligning rank clocks on a shared anchor span')
    ap.add_argument('inputs', nargs='+', help='per-rank trace JSON files')
    ap.add_argument('-o', '--output', required=True)
    ap.add_argument('--ranks', default=None,
                    help='comma-separated rank per input, in order '
                         '(default: rank<N> in the filename, else '
                         'input position)')
    ap.add_argument('--anchor', default=DEFAULT_ANCHOR,
                    help='span whose END aligns the rank clocks '
                         '(default %(default)r: barriers release every '
                         'rank at the same real instant)')
    ap.add_argument('--no-align', action='store_true',
                    help='merge raw timestamps (pre-alignment behavior)')
    ap.add_argument('--no-relane', action='store_true',
                    help='keep serving events on their raw worker '
                         'tids instead of one lane per (model, '
                         'replica)')
    args = ap.parse_args(argv)
    ranks = [int(r) for r in args.ranks.split(',')] if args.ranks \
        else None
    doc = merge(args.inputs, ranks, anchor=args.anchor,
                align=not args.no_align, relane=not args.no_relane)
    with open(args.output, 'w') as f:
        json.dump(doc, f)
    errors = check_trace.validate_file(args.output)
    if errors:
        for msg in errors[:20]:
            print('%s: %s' % (args.output, msg), file=sys.stderr)
        return 1
    n_data = sum(1 for e in doc['traceEvents'] if e.get('ph') != 'M')
    print('%s: %d events across %d rank(s) OK'
          % (args.output, n_data, len(args.inputs)))
    return 0


if __name__ == '__main__':
    sys.exit(main())
