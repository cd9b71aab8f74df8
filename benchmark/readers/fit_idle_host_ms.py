"""Device idle time a step that the host explains: the idle gaps of
chip 0 of 0.1 ms or more inside the traced slice, each given by
``trace_reduce.attribute_gaps``'s rule to the program's span that covers
at least half of it; the sum of the gaps so named, over the slice's
steps.  Every such gap goes to a ``[bench]`` line with its name."""
from . import fit_span_tree
from ..harness import log


def read(slice_):
    tree = fit_span_tree.of_slice(slice_)
    if tree is None:
        return None
    for start, end, name in tree.named_gaps():
        log('idle gap on chip 0: %.3f ms at +%.3f ms %s (under it: %s)' % (
            (end - start) / 1e6, (start - tree.window[0]) / 1e6, name,
            tree.under(start, end) or 'no span of the program'))
    return tree.idle_host_ms(slice_['steps'])
