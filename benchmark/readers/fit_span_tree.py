"""The program's own spans in the traced slice of a fit cell: what
``fit_host_step_ms``, ``fit_callback_ms`` and ``fit_idle_host_ms`` share.
Not a reader itself.

Since PR 26 every ``perfwatch.phase`` of the program is also a
``jax.profiler`` annotation named ``mxtpu.`` + its histogram, on the
thread that did the work and on the device planes' clock, and every
iteration of the fit loop has a root, ``mxtpu.perf.fit_step``, from
asking for the batch to the last batch-end callback's return.  The
benchmark's own spans start with ``bench.``.

The profiler records a span when it ends, and only while the session
runs.  The driver starts and stops the session from a batch-end
callback, so the root that is open at either end of ``bench.slice`` is
not in the trace: of ``slice:steps`` steps the last has no root (and no
``callbacks``, and its ``bench.batch_end`` with the closing drain is
missing too), while its finished children are there.  The two host-side
metrics are therefore means over the roots (or ``callbacks`` spans) the
trace holds, not over ``slice:steps``; the tree's ``coverage`` says how
much of the slice those roots tile.

A trace without a TPU plane (the CPU rehearsal) or without a root (a
program from before PR 26) gives no tree, and the metrics are left out.
"""
import collections
import functools
import glob
import os

from .. import harness
from .. import trace_reduce as tr
from ..harness import log

PROGRAM = 'mxtpu.'
BENCH = 'bench.'
ROOT = PROGRAM + 'perf.fit_step'
PHASE = PROGRAM + 'perf.phase.'
WINDOW_WAIT = PHASE + 'window_wait'
FEED_WAIT = PHASE + 'feed_wait'
CALLBACKS = PHASE + 'callbacks'
MIN_GAP_NS = 100000         # the gaps worth a name: 0.1 ms and longer

# host_spans reads every host thread at once; a profile of one line
# makes it read one thread
_Plane = collections.namedtuple('_Plane', 'name lines')
_Profile = collections.namedtuple('_Profile', 'planes')


def threads(profile):
    """``trace_reduce.host_spans`` thread by thread: for each host thread
    its program's spans and the benchmark's, each a sorted list of
    ``(start, end, name)``."""
    found = []
    for plane in profile.planes:
        if plane.name != tr.HOST_PLANE:
            continue
        for line in plane.lines:
            alone = _Profile([_Plane(tr.HOST_PLANE, [line])])
            found.append((tr.host_spans(alone, PROGRAM),
                          tr.host_spans(alone, BENCH)))
    return found


def clip(spans, lo, hi):
    return [(max(s, lo), min(e, hi), name) for s, e, name in spans
            if e > lo and s < hi]


def _cover(spans, names=None):
    return tr.union([(s, e) for s, e, name in spans
                     if names is None or name in names])


class SpanTree(object):
    """The fit thread's roots and children, the benchmark's spans on
    that thread, every thread's ``mxtpu.`` spans and chip 0's idle
    gaps, all clipped to ``bench.slice``."""

    def __init__(self, profile, window, chip):
        lo, hi = self.window = window
        self.fit, self.bench, self.roots, self.everywhere = [], [], [], []
        for program, bench in threads(profile):
            program = clip(program, lo, hi)
            self.everywhere.extend(program)
            roots = [s for s in program if s[2] == ROOT]
            if len(roots) > len(self.roots):
                # the fit thread; the benchmark's spans on it are its
                # callback and the drains in it (its iterator runs on
                # the feed thread)
                self.fit, self.roots = program, roots
                self.bench = [s for s in clip(bench, lo, hi)
                              if s[2] != harness.SLICE_SPAN]
        self.everywhere.sort()
        self.idle = [g for g in tr.gaps(tr.DeviceOps(chip, lo, hi).busy,
                                        lo, hi)
                     if g[1] - g[0] >= MIN_GAP_NS]

    @property
    def coverage(self):
        """The share of the slice that the recorded roots tile."""
        lo, hi = self.window
        return tr.total(_cover(self.roots)) / float(hi - lo)

    def host_step_ms(self):
        """The fit thread's own work a step: the roots' time less the
        time under ``window_wait``, ``feed_wait`` and the benchmark's
        spans, over the number of roots."""
        own = tr.subtract(_cover(self.roots), tr.union(
            _cover(self.fit, (WINDOW_WAIT, FEED_WAIT)) +
            _cover(self.bench)))
        return tr.total(own) / 1e6 / len(self.roots)

    def callback_ms(self):
        """The batch-end callbacks a step, less the benchmark's own
        spans inside them; None if the program ran none."""
        calls = [s for s in self.fit if s[2] == CALLBACKS]
        if not calls:
            return None
        own = tr.subtract(_cover(calls), _cover(self.bench))
        return tr.total(own) / 1e6 / len(calls)

    def named_gaps(self):
        """``(start, end, name)`` of every idle gap of 0.1 ms or more on
        chip 0, in time order, each named by ``attribute_gaps``'s rule
        over the program's spans of every thread.  The root and
        ``window_wait`` are no candidates: the host waiting for the
        device explains no gap of the device."""
        candidates = [s for s in self.everywhere
                      if s[2] not in (ROOT, WINDOW_WAIT)]
        return [(start, end,
                 tr.attribute_gaps([(start, end)], candidates, most=1)[0][0])
                for start, end in self.idle]

    def under(self, start, end):
        """For the log: which of the program's spans lie under one gap,
        as ``name share%``, largest first (the waits too: they explain
        nothing, but they say where the fit thread was)."""
        cover = {}
        for s, e, name in clip(self.everywhere, start, end):
            if name != ROOT:
                cover[name] = cover.get(name, 0) + e - s
        return ', '.join(
            '%s %.0f%%' % (name[len(PHASE):], 100.0 * ns / (end - start))
            for name, ns in sorted(cover.items(), key=lambda kv: -kv[1]))

    def idle_host_ms(self, steps):
        """Device idle time a step that a span of the program names."""
        named = [end - start for start, end, name in self.named_gaps()
                 if name != tr.UNATTRIBUTED]
        return sum(named) / 1e6 / steps

    def self_ms(self):
        """What a root holds that no child names, a root: its length
        less its direct children (``metric_drain`` nests in
        ``callbacks``) and the benchmark's spans."""
        children = _cover([s for s in self.fit if s[2] != ROOT])
        own = tr.subtract(_cover(self.roots),
                          tr.union(children + _cover(self.bench)))
        return tr.total(own) / 1e6 / len(self.roots)

    def describe(self):
        """The tree on ``[bench]`` lines: the means a root, and one
        root (the one of median length) with its children's times."""
        lo, hi = self.window
        log('span tree: %d roots tile %.2f%% of the %.3f s slice; a root '
            '%.3f ms, of it the host\'s own work %.3f ms, unnamed '
            '(self time) %.3f ms' % (
                len(self.roots), 100.0 * self.coverage, (hi - lo) / 1e9,
                tr.total(_cover(self.roots)) / 1e6 / len(self.roots),
                self.host_step_ms(), self.self_ms()))
        sums = collections.OrderedDict()
        for s, e, name in self.everywhere:
            if name != ROOT:
                count, ns = sums.get(name, (0, 0))
                sums[name] = (count + 1, ns + e - s)
        log('span means, ms (times in the slice): ' + ', '.join(
            '%s %.3f (%d)' % (name[len(PROGRAM):], ns / 1e6 / count, count)
            for name, (count, ns) in sums.items()))
        start, end, _ = sorted(self.roots,
                               key=lambda r: r[1] - r[0])[len(self.roots) // 2]
        inside = [s for s in self.fit + self.bench
                  if s[2] != ROOT and s[0] >= start and s[1] <= end]
        log('one root, %.3f ms: ' % ((end - start) / 1e6) + ', '.join(
            '%s %.3f at +%.3f' % (name.replace(PHASE, ''), (e - s) / 1e6,
                                  (s - start) / 1e6)
            for s, e, name in sorted(inside)))


def tree_of(profile, slice_span=harness.SLICE_SPAN):
    """The tree of one profile, or None when it holds no TPU plane, no
    slice or no root."""
    chips = tr.device_planes(profile)
    window = tr.window_of(profile, slice_span)
    if not chips or window is None:
        return None
    tree = SpanTree(profile, window, chips[0])
    return tree if tree.roots else None


@functools.lru_cache(maxsize=1)
def _tree_at(path):
    """Read once a run, described once: the three readers share it."""
    tree = tree_of(tr.load(path))
    if tree is not None:
        tree.describe()
    return tree


def of_slice(slice_):
    """The tree of the run's traced slice.  ``slice_`` carries the
    reduced trace and not its path, so this finds the file as
    ``harness.SliceTrace.reduced`` does: the newest ``.xplane.pb`` under
    ``harness.TRACE_DIR``."""
    paths = glob.glob(os.path.join(harness.TRACE_DIR, '**', '*.xplane.pb'),
                      recursive=True)
    if not paths or not slice_.get('steps'):
        return None
    return _tree_at(max(paths, key=os.path.getmtime))
