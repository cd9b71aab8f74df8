"""Programs compiled or fetched from the persistent cache before the
window: the counter ``compile.programs`` (one a backend compile)."""
from . import setup_snapshot


def read(slice_):
    snap = setup_snapshot.at_start(slice_)
    if snap is None:
        return None
    count = snap.get('counters', {}).get('compile.programs')
    return None if count is None else float(count)
