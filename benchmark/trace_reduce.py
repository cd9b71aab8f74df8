"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time and idle share, device time per step, the ops
that took most time, convolution and collective time, the exposed part
of the collectives, and what the host was doing in the longest idle gaps.

How a v5e trace is laid out (looked at by hand, PR 23): one plane per
chip, ``/device:TPU:<n>``, with the lines ``XLA Modules`` (one event per
executed program), ``XLA Ops`` (one event per executed HLO op, in order,
never overlapping on a chip) and ``Async XLA Ops`` (the span from an
asynchronous op's start to its done: copies between memory spaces, and
collectives).  An op event's name is the whole HLO instruction,
``%name = type opcode(operands), kind=..., calls=...``.  Host threads
are lines of the plane ``/host:CPU``; ``jax.profiler.TraceAnnotation``
spans appear there under their own names, on the same clock as the
device planes.

Convolutions: on the TPU every convolution and matrix product is the
root of an output fusion, and nothing else is, so an op counts as
convolution time when its text says ``kind=kOutput`` or its opcode is
``convolution`` or ``dot`` (checked against the compiled ResNet-50 step:
162 ``kOutput`` fusions, each holding a convolution or the FC's dot, and
no ``kLoop`` fusion holding one).
"""
import re

DEVICE_PLANE = '/device:TPU:'
HOST_PLANE = '/host:CPU'
OPS_LINE = 'XLA Ops'
ASYNC_LINE = 'Async XLA Ops'
COLLECTIVES = ('all-reduce', 'reduce-scatter', 'all-gather', 'all-to-all',
               'collective-permute', 'collective-broadcast')
UNATTRIBUTED = 'inside-the-program'

_OPCODE = re.compile(r'^(?:\(.*?\)|\S+)\s+([a-z][\w\-]*)\(')


def load(path):
    import jax
    return jax.profiler.ProfileData.from_file(path)


# -- interval arithmetic (lists of (start, end), in ns) -------------------

def union(intervals):
    """Sorted, disjoint intervals covering the same points."""
    merged = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def total(intervals):
    return sum(end - start for start, end in intervals)


def subtract(a, b):
    """The part of the disjoint sorted intervals ``a`` that no interval
    of the disjoint sorted ``b`` covers."""
    out, j = [], 0
    for start, end in a:
        cursor = start
        while j < len(b) and b[j][1] <= cursor:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cursor:
                out.append((cursor, b[k][0]))
            cursor = max(cursor, b[k][1])
            k += 1
        if cursor < end:
            out.append((cursor, end))
    return out


def gaps(busy, lo, hi):
    return subtract([(lo, hi)], busy)


# -- reading op events ----------------------------------------------------

def opcode(text):
    """The HLO opcode of an op event's text ('' if it has none)."""
    _, _, rhs = text.partition(' = ')
    match = _OPCODE.match(rhs)
    return match.group(1) if match else ''


_SHAPE = re.compile(r'[a-z]+\d*\[[\d,]*\]')


def op_label(text):
    """A short stable label: the op's name, first result shape, opcode."""
    name, _, rhs = text.partition(' = ')
    shape = _SHAPE.search(rhs)
    return ' '.join(filter(None, (name, shape.group(0) if shape else '',
                                  opcode(text))))[:120]


def is_convolution(text):
    return 'kind=kOutput' in text or opcode(text) in ('convolution', 'dot')


def is_collective(text):
    return opcode(text).startswith(COLLECTIVES)


class DeviceOps(object):
    """One chip's op events inside a window."""

    def __init__(self, plane, lo, hi):
        self.name = plane.name
        self.sync = []      # (start, end, text) on the XLA Ops line
        self.spans = []     # the same for Async XLA Ops
        for line in plane.lines:
            if line.name not in (OPS_LINE, ASYNC_LINE):
                continue
            into = self.sync if line.name == OPS_LINE else self.spans
            for event in line.events:
                start, end = event.start_ns, event.start_ns + event.duration_ns
                if end > lo and start < hi:
                    into.append((max(start, lo), min(end, hi), event.name))
        self.busy = union((s, e) for s, e, _ in self.sync)


def device_planes(profile):
    planes = [p for p in profile.planes if p.name.startswith(DEVICE_PLANE)]
    return sorted(planes, key=lambda p: int(p.name[len(DEVICE_PLANE):]))


def host_spans(profile, prefix='bench.'):
    """``(start, end, name)`` of every host annotation whose name starts
    with ``prefix``, over all host threads."""
    found = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith(prefix):
                    found.append((event.start_ns,
                                  event.start_ns + event.duration_ns,
                                  event.name))
    return sorted(found)


def window_of(profile, span_name):
    """The (start, end) of the one host annotation named ``span_name``
    (the benchmark puts one round the measured slice), or None."""
    spans = host_spans(profile, span_name)
    spans = [s for s in spans if s[2] == span_name]
    if not spans:
        return None
    return min(s[0] for s in spans), max(s[1] for s in spans)


def attribute_gaps(idle, spans, most=5):
    """The ``most`` longest idle gaps as ``[name, seconds]``: a gap is
    named after the host span that covers the largest part of it, if
    that is at least half; otherwise the benchmark's own spans do not
    explain it and it is ``inside-the-program``."""
    out = []
    for start, end in sorted(idle, key=lambda g: g[0] - g[1])[:most]:
        cover = {}
        for s, e, name in spans:
            if e > start and s < end:
                cover[name] = cover.get(name, 0) + min(e, end) - max(s, start)
        name = max(cover, key=cover.get) if cover else None
        if name is None or cover[name] < 0.5 * (end - start):
            name = UNATTRIBUTED
        out.append([name, (end - start) / 1e9])
    return out


def reduce_profile(profile, span_name='bench.slice', chips=None):
    """Everything the benchmark reads from one traced slice.

    Times are seconds.  ``busy_s``, ``conv_s``, ``collective_s`` and
    ``collective_exposed_s`` are means over the chips used; ``device_ops``
    and ``idle_gaps`` are of the first chip."""
    window = window_of(profile, span_name)
    planes = device_planes(profile)
    if chips is not None:
        planes = planes[:chips]
    if not planes:
        return None
    if window is None:
        starts = [e.start_ns for p in planes for l in p.lines
                  if l.name == OPS_LINE for e in l.events]
        ends = [e.start_ns + e.duration_ns for p in planes for l in p.lines
                if l.name == OPS_LINE for e in l.events]
        if not starts:
            return None
        window = (min(starts), max(ends))
    lo, hi = window
    devices = [DeviceOps(p, lo, hi) for p in planes]
    n = float(len(devices))
    busy = conv = coll = exposed = 0.0
    for dev in devices:
        busy += total(dev.busy)
        conv += sum(e - s for s, e, text in dev.sync if is_convolution(text))
        comm = union([(s, e) for s, e, text in dev.sync + dev.spans
                      if is_collective(text)])
        other = union([(s, e) for s, e, text in dev.sync
                       if not is_collective(text)])
        coll += total(comm)
        exposed += total(subtract(comm, other))
    first = devices[0]
    per_op = {}
    for s, e, text in first.sync:
        label = op_label(text)
        per_op[label] = per_op.get(label, 0.0) + (e - s)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    return {
        'window_s': (hi - lo) / 1e9,
        'chips': len(devices),
        'busy_s': busy / n / 1e9,
        'conv_s': conv / n / 1e9,
        'collective_s': coll / n / 1e9,
        'collective_exposed_s': exposed / n / 1e9,
        'op_events': sum(len(d.sync) for d in devices),
        'device_ops': [[label, ns / 1e9] for label, ns in top],
        'idle_gaps': attribute_gaps(
            gaps(first.busy, lo, hi),
            [s for s in host_spans(profile) if s[2] != span_name]),
    }
