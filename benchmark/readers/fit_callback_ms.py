"""The batch-end callbacks a step: ``mxtpu.perf.phase.callbacks`` on the
fit thread inside the traced slice less the benchmark's own ``bench.*``
spans inside them, a ``callbacks`` span (``fit_span_tree``)."""
from . import fit_span_tree


def read(slice_):
    tree = fit_span_tree.of_slice(slice_)
    return tree.callback_ms() if tree else None
