"""Python's full collections before the window: the sum of ``perf.gc``
(``perfwatch``'s callback, one span a generation-2 collection)."""
from . import setup_snapshot


def read(slice_):
    return setup_snapshot.histogram_sums(slice_, 'perf.gc')
