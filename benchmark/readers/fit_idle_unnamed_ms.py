"""Device idle time a step that no span of the program names, the
complement of ``fit_idle_host_ms``: chip 0's idle gaps of 0.1 ms or more
inside the traced slice that ``fit_span_tree.SpanTree.named_gaps`` leaves
``inside-the-program`` (no ``mxtpu.*`` span of any thread covers half of
it; ``window_wait`` and the root are no candidates), summed over the
slice's steps.  Every such gap goes to a ``[bench]`` line with its offset
in the slice and the program's spans under it."""
from . import fit_span_tree
from .. import trace_reduce as tr
from ..harness import log


def under(tree, start, end):
    """The program's spans under one gap, ``name share%``, largest first:
    every ``mxtpu.*`` name whole, the collector's and set-up's too."""
    cover = {}
    for s, e, name in fit_span_tree.clip(tree.everywhere, start, end):
        if name != fit_span_tree.ROOT:
            cover[name] = cover.get(name, 0) + e - s
    return ', '.join(
        '%s %.0f%%' % (name[len(fit_span_tree.PROGRAM):],
                       100.0 * ns / (end - start))
        for name, ns in sorted(cover.items(), key=lambda kv: -kv[1]))


def read(slice_):
    tree = fit_span_tree.of_slice(slice_)
    if tree is None:
        return None
    unnamed = [(start, end) for start, end, name in tree.named_gaps()
               if name == tr.UNATTRIBUTED]
    for start, end in unnamed:
        log('idle gap on chip 0 that no span names: %.3f ms at +%.3f ms '
            '(under it: %s)' % ((end - start) / 1e6,
                                (start - tree.window[0]) / 1e6,
                                under(tree, start, end) or
                                'no span of the program'))
    return sum(end - start for start, end in unnamed) / 1e6 / slice_['steps']
