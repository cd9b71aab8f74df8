"""The fit thread's own work a step: on that thread inside the traced
slice, the roots' time (``mxtpu.perf.fit_step``) less the time under
``mxtpu.perf.phase.window_wait``, ``mxtpu.perf.phase.feed_wait`` and the
benchmark's own ``bench.*`` spans, a root (``fit_span_tree``)."""
from . import fit_span_tree


def read(slice_):
    tree = fit_span_tree.of_slice(slice_)
    return tree.host_step_ms() if tree else None
