"""XLA's compiles before the window, less the persistent cache's reads:
``compile.backend_secs`` less ``compile.cache_read_secs`` (none read is
0).  The backend event also holds a miss's write to the cache."""
from . import setup_snapshot


def read(slice_):
    snap = setup_snapshot.at_start(slice_)
    if snap is None:
        return None
    backend = setup_snapshot.histogram_sum(snap, 'compile.backend_secs')
    if backend is None:
        return None
    return backend - setup_snapshot.histogram_sum(
        snap, 'compile.cache_read_secs', absent=0.0)
