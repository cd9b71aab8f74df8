"""Tracing to jaxprs and lowering to MLIR before the window: the sums of
``compile.trace_secs`` and ``compile.lower_secs`` (``compile_cache``'s
listener; a compile inside another counts in the innermost only)."""
from . import setup_snapshot


def read(slice_):
    return setup_snapshot.histogram_sums(
        slice_, 'compile.trace_secs', 'compile.lower_secs')
