"""Communication-attribution plane — per-executable collective
accounting, comm-vs-compute roofline split, cross-rank step cadence.

PR 8 moved the whole multi-chip data plane INSIDE the compiled program:
XLA's SPMD partitioner now emits the gradient all-reduce, the ZeRO
reduce-scatter/all-gather pair and any tp collectives as HLO
instructions the host never sees.  That is the right place for them
(PAPERS.md 1802.06949: collectives belong in the dataflow graph, not a
host loop) — but it left scaling efficiency unattributable: ``perfwatch``
could say a step was slow, not whether the milliseconds went to compute,
to the interconnect, or to one straggling rank.  The MXNet paper's
1→256-GPU scaling claim (Chen et al., 1512.01274) lives or dies on
exactly that attribution.  This module is the missing sense, three legs
riding the PR-1 registry (and therefore the PR-5 telemetry piggyback —
a cluster reports per-rank comm/step-time centrally for free):

1. **Per-executable collective accounting** — :func:`analyze_executable`
   (invoked from every ``perfwatch.register_executable`` site: the
   warm-start AOT pool, the hot-path AOT capture in
   ``Module._run_fused``, Predictor/Executor forwards) walks the
   compiled program's HLO text and records, per collective kind
   (all-reduce, all-gather, reduce-scatter, all-to-all,
   collective-permute), the instruction count, the payload bytes and the
   analytic per-device *wire* bytes (ring-schedule model:
   ``2·N·(g-1)/g`` for an all-reduce over a group of ``g``, ``N·(g-1)/g``
   for gather/scatter legs) as ``comm.<kind>[<sig>].{count,bytes}``
   gauges plus per-kind totals; the stepping executable's wire total is
   published as ``comm.bytes_per_step``.

2. **Comm-vs-compute roofline split** — :func:`on_step` (called from
   ``perfwatch.note_step``) models one step as a compute leg
   (per-device FLOPs over the chip peak, ``perfwatch.PEAKS``) plus a
   communication leg (wire bytes over the interconnect peak,
   :data:`ICI_PEAKS` beside it; ``MXTPU_PEAK_BW`` override) and
   publishes ``perf.comm_fraction`` = t_comm / (t_comm + t_compute) ∈
   [0, 1] — the number that says whether buying faster chips or a
   fatter interconnect moves the step time.

3. **Cross-rank step cadence** — every step's dispatch-to-dispatch
   interval lands in a ``comm.step_time`` histogram and every dist
   barrier's wait in ``comm.barrier_wait``; both ride the heartbeat
   telemetry piggyback (old servers structurally ignore them), and the
   kv server derives a ``cluster.step_skew`` gauge + slowest-rank
   attribution from the per-rank views (``kvstore_server.
   compute_step_skew``), with ``MXTPU_SKEW_WARN_PCT`` arming the
   health plane's laggard warning + flight record
   (``health.note_skew``).

Zero overhead off: every hook is one module-global check
(``tests/test_commwatch.py`` pins < 2x a same-shape inlined floor).
``MXTPU_COMMWATCH=1`` implies the metrics registry, the same contract
as MXTPU_PROFILE / MXTPU_PERFWATCH.
"""
from __future__ import annotations

import re
import sys
import threading

from . import config, instrument, perfwatch

__all__ = [
    'enabled', 'set_enabled', 'refresh', 'activate_fit',
    'ICI_PEAKS', 'interconnect_bw',
    'COLLECTIVE_KINDS', 'parse_collectives', 'collective_stats',
    'wire_bytes', 'analyze_executable', 'program_info', 'programs',
    'clear_programs',
    'comm_fraction', 'on_step', 'barrier_wait',
]

# Peak per-chip interconnect bandwidth (bytes/sec, all links combined)
# per device kind — the denominator of the communication roofline leg,
# the sibling of perfwatch.PEAKS (TPU v5 lite: 1,600 Gbit/s, Google
# Cloud documentation, "TPU v5e").  An unknown kind is an error;
# MXTPU_PEAK_BW pins the figure explicitly (CPU tests do).
ICI_PEAKS = {
    'TPU v5 lite': 200e9,
    'TPU v5': 600e9,
    'TPU v4': 300e9,
    'TPU v6 lite': 400e9,
}

_on = False
_lock = threading.Lock()

# (kind, keystr) -> {'kind','key','collectives': {ckind: {'count',
#                    'bytes','wire_bytes'}}, 'wire_bytes_per_step',
#                    'num_devices'}
_programs = {}


# ---------------------------------------------------------------------------
# Enablement
# ---------------------------------------------------------------------------

def refresh():
    """(Re)read MXTPU_COMMWATCH.  Called at import and per fit
    (``perfwatch.activate_fit``); hot-path hooks read the cached module
    global only."""
    global _on
    _on = bool(config.get('MXTPU_COMMWATCH'))
    perfwatch._comm_on = _on
    if _on and not instrument.metrics_enabled():
        # the plane's output IS the metrics registry — implied on, the
        # same contract as MXTPU_PROFILE / MXTPU_PERFWATCH
        instrument.set_metrics(True)


def set_enabled(on):
    """Runtime toggle (tests; equivalent to exporting MXTPU_COMMWATCH)."""
    global _on
    _on = bool(on)
    perfwatch._comm_on = _on
    if _on and not instrument.metrics_enabled():
        instrument.set_metrics(True)


def enabled():
    return _on


def activate_fit():
    """Per-fit activation (rides ``perfwatch.activate_fit``): re-read
    the knob so an env var exported between fits takes effect."""
    refresh()


# ---------------------------------------------------------------------------
# Interconnect peaks
# ---------------------------------------------------------------------------

def interconnect_bw(kind=None):
    """Peak interconnect bytes/sec for the comm-roofline denominator:
    the MXTPU_PEAK_BW override when set, else :data:`ICI_PEAKS` by
    device kind (the attached device's when None).  An unknown kind
    raises."""
    override = float(config.get('MXTPU_PEAK_BW'))
    if override > 0:
        return override
    return perfwatch.lookup_peak(
        ICI_PEAKS, perfwatch.device_kind() if kind is None else kind,
        'MXTPU_PEAK_BW')


# ---------------------------------------------------------------------------
# Leg 1: HLO collective accounting
# ---------------------------------------------------------------------------

COLLECTIVE_KINDS = ('all-reduce', 'all-gather', 'reduce-scatter',
                    'all-to-all', 'collective-permute')

# bytes per element per HLO primitive type (the shapes in the compiled
# module text); f8 variants all serialize one byte per element
_DTYPE_BYTES = {
    'pred': 1, 's8': 1, 'u8': 1, 's16': 2, 'u16': 2, 's32': 4, 'u32': 4,
    's64': 8, 'u64': 8, 'f16': 2, 'bf16': 2, 'f32': 4, 'f64': 8,
    'c64': 8, 'c128': 16,
}

# one DEFINING collective instruction: everything between '=' and the
# op name is the result shape (possibly a tuple); '-done' halves of
# async pairs are skipped (their shapes repeat the '-start') and
# operand REFERENCES never match because the op name must be followed
# directly by '('
_COLL_RE = re.compile(
    r'=\s*(?P<shape>[^=]*?)\s*'
    r'(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|'
    r'collective-permute)(?P<start>-start)?\(')

_SHAPE_RE = re.compile(r'(?P<dt>[a-z]\d*[a-z0-9]*)\[(?P<dims>[0-9,]*)\]')

_GROUPS_BRACE_RE = re.compile(r'replica_groups=\{\{([0-9, ]+)\}')
_GROUPS_IOTA_RE = re.compile(r'replica_groups=\[(\d+),(\d+)\]<=')


def _shape_bytes_each(segment):
    """Bytes of each ``dtype[dims]`` shape token in ``segment``, in
    order (layout suffixes ``{1,0}`` never match the shape regex)."""
    out = []
    for m in _SHAPE_RE.finditer(segment):
        dt = m.group('dt')
        if dt.startswith('f8'):
            esize = 1
        else:
            esize = _DTYPE_BYTES.get(dt)
        if esize is None:
            continue
        n = 1
        dims = m.group('dims')
        if dims:
            for d in dims.split(','):
                n *= int(d)
        out.append(n * esize)
    return out


def _shape_bytes(segment):
    """Total bytes of every shape token in ``segment`` (a tuple LHS
    sums its members — the multi-operand SYNC collective form)."""
    return sum(_shape_bytes_each(segment))


def _group_size(line, num_devices):
    """Collective group size from the instruction's replica_groups
    attribute: explicit ``{{0,2},{1,3}}`` lists, the iota form
    ``[G,S]<=...`` (G groups of S), or — absent — the whole mesh."""
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return max(1, int(m.group(2)))
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return max(1, len([t for t in m.group(1).split(',') if
                           t.strip() != '']))
    return max(1, int(num_devices))


def wire_bytes(kind, nbytes, group):
    """Analytic per-device wire traffic of ONE execution of a
    collective whose result payload is ``nbytes`` over a group of
    ``group`` devices — the ring-schedule model every interconnect
    roofline uses:

    - all-reduce: ``2·N·(g-1)/g`` (reduce-scatter + all-gather halves);
    - all-gather: the result is the GATHERED tensor, each device
      receives the other ``g-1`` shards → ``N·(g-1)/g``;
    - reduce-scatter: the result is one SHARD, each device sends
      ``g-1`` shard-sized messages → ``N·(g-1)``;
    - all-to-all: every device exchanges ``(g-1)/g`` of its payload;
    - collective-permute: the payload crosses one link once.
    """
    g = max(1, int(group))
    n = float(nbytes)
    if g == 1:
        return 0.0 if kind != 'collective-permute' else n
    if kind == 'all-reduce':
        return 2.0 * n * (g - 1) / g
    if kind == 'all-gather':
        return n * (g - 1) / g
    if kind == 'reduce-scatter':
        return n * (g - 1)
    if kind == 'all-to-all':
        return n * (g - 1) / g
    if kind == 'collective-permute':
        return n
    return 0.0


def parse_collectives(hlo_text, num_devices=1):
    """Every DEFINING collective instruction in an HLO module text as
    ``[(kind, result_bytes, group_size)]``.  Async pairs count once (the
    ``-start`` half carries the shape; ``-done`` is skipped), operand
    references never match, and sharding-annotation strings inside
    ``metadata=`` cannot produce instructions.

    A SYNC instruction's tuple LHS is multiple operands reduced
    together — its members sum.  An ASYNC ``-start``'s tuple LHS is
    ``(operand, result[, contexts...])`` — only the result slot is
    payload (counting the operand too would double all-gather/permute
    traffic on backends whose scheduler emits the async form)."""
    out = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group('op')
        toks = _shape_bytes_each(m.group('shape'))
        if m.group('start') and len(toks) >= 2:
            nbytes = toks[1]
        else:
            nbytes = sum(toks)
        out.append((kind, nbytes, _group_size(line, num_devices)))
    return out


def collective_stats(hlo_text, num_devices=1):
    """Aggregate :func:`parse_collectives` per kind:
    ``{kind: {'count', 'bytes', 'wire_bytes'}}`` (bytes = result
    payload, wire_bytes = analytic per-device traffic)."""
    stats = {}
    for kind, nbytes, group in parse_collectives(hlo_text, num_devices):
        s = stats.setdefault(kind, {'count': 0, 'bytes': 0.0,
                                    'wire_bytes': 0.0})
        s['count'] += 1
        s['bytes'] += nbytes
        s['wire_bytes'] += wire_bytes(kind, nbytes, group)
    return stats


def _kind_gauge(ckind):
    return 'comm.' + ckind.replace('-', '_')


def analyze_executable(kind, key, compiled, num_devices=1):
    """Collective accounting for one registered executable (called by
    ``perfwatch.register_executable`` — i.e. at every AOT compile site
    in the tree).  Publishes per-program
    ``comm.<ckind>[<key>].{count,bytes}`` gauges, per-kind running
    totals (``comm.<ckind>.{count,bytes}`` — what the analytic checks
    read without knowing program hashes), and keeps
    the row for :func:`on_step`'s per-step attribution.  Idempotent per
    (kind, key); never raises; returns the row or None."""
    if not _on:
        return None
    try:
        kind = str(kind)
        keystr = perfwatch._keystr(key)
        with _lock:
            row = _programs.get((kind, keystr))
        if row is not None:
            return row
        # the compiled (post-SPMD-partitioning) HLO text
        stats = collective_stats(compiled.as_text(), num_devices)
        total_wire = sum(s['wire_bytes'] for s in stats.values())
        row = {'kind': kind, 'key': keystr,
               'num_devices': max(1, int(num_devices)),
               'collectives': stats,
               'wire_bytes_per_step': total_wire}
        with _lock:
            _programs[(kind, keystr)] = row
            totals = {}
            for r in _programs.values():
                for ck, s in r['collectives'].items():
                    t = totals.setdefault(ck, [0, 0.0, 0.0])
                    t[0] += s['count']
                    t[1] += s['bytes']
                    t[2] += s['wire_bytes']
        stem = '%s[%s]' % (kind, keystr)
        for ck, s in stats.items():
            g = _kind_gauge(ck)
            instrument.set_gauge('%s[%s].count' % (g, keystr), s['count'])
            instrument.set_gauge('%s[%s].bytes' % (g, keystr), s['bytes'])
        for ck, (c, b, w) in totals.items():
            g = _kind_gauge(ck)
            instrument.set_gauge(g + '.count', c)
            instrument.set_gauge(g + '.bytes', b)
            instrument.set_gauge(g + '.wire_bytes', w)
        instrument.set_gauge('comm.executables', len(_programs))
        instrument.set_gauge('xla.%s.comm_wire_bytes' % stem, total_wire)
        return row
    except Exception:
        return None


def program_info(kind, key):
    with _lock:
        row = _programs.get((str(kind), perfwatch._keystr(key)))
        return dict(row) if row else None


def programs():
    """Snapshot of every analyzed program row (report/forensics)."""
    with _lock:
        return [dict(v) for v in _programs.values()]


def clear_programs():
    with _lock:
        _programs.clear()


# ---------------------------------------------------------------------------
# Leg 2+3: per-step roofline split + cross-rank cadence
# ---------------------------------------------------------------------------

def comm_fraction(wire_bytes_step, flops_per_device, peak_flops=None,
                  peak_bw=None):
    """t_comm / (t_comm + t_compute) for one step: the fraction of an
    ideally-overlapped step that the interconnect leg needs.  0.0 when
    the step moves no collective bytes, 1.0 when it does nothing else;
    by construction always in [0, 1]."""
    peak_bw = peak_bw if peak_bw else interconnect_bw()
    peak_flops = peak_flops if peak_flops else perfwatch.peak_flops()
    t_comm = float(wire_bytes_step) / peak_bw if peak_bw else 0.0
    t_comp = float(flops_per_device) / peak_flops if peak_flops else 0.0
    total = t_comm + t_comp
    return t_comm / total if total > 0 else 0.0


def on_step(kind, key, interval, flops_per_device):
    """One step completed dispatch (called from ``perfwatch.note_step``
    when this plane is on): record the dispatch-to-dispatch interval in
    the ``comm.step_time`` histogram (what the kv server's skew
    attribution reads off the telemetry piggyback) and publish
    ``comm.bytes_per_step`` + ``perf.comm_fraction`` from the stepping
    executable's analyzed wire bytes."""
    if not _on:
        return
    if interval is not None and interval > 0:
        instrument.observe_hist('comm.step_time', interval)
    row = None
    if key is not None:
        with _lock:
            row = _programs.get((str(kind), perfwatch._keystr(key)))
    if row is None:
        return
    wire = row['wire_bytes_per_step']
    instrument.set_gauge('comm.bytes_per_step', wire)
    instrument.set_gauge('perf.comm_fraction',
                         comm_fraction(wire, flops_per_device))


def barrier_wait(seconds):
    """One dist-barrier wait completed: ``comm.barrier_wait`` histogram
    + ``comm.barriers`` counter (the cross-rank wait-time signal of the
    straggler story).  One flag check when off."""
    if not _on:
        return
    instrument.observe_hist('comm.barrier_wait', seconds)
    instrument.inc('comm.barriers')


# register with perfwatch: its register_executable/note_step/
# activate_fit consult this module through the _comm hook (perfwatch
# cannot import commwatch at module top — this direction is the cycle
# breaker)
perfwatch._comm = sys.modules[__name__]
refresh()
