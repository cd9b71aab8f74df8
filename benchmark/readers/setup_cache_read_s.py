"""The persistent cache's reads of programs before the window:
``compile.cache_read_secs``, 0 where nothing was read from it, given
that the program times its backend compiles (``compile.backend_secs``)."""
from . import setup_snapshot


def read(slice_):
    snap = setup_snapshot.at_start(slice_)
    if snap is None or \
            setup_snapshot.histogram_sum(snap, 'compile.backend_secs') is None:
        return None
    return setup_snapshot.histogram_sum(snap, 'compile.cache_read_secs',
                                        absent=0.0)
