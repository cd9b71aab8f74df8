"""What the six ``setup_*`` readers share: the program's series in the
snapshot every driver takes at the window's start (``slice['snap0']``).
The registry counts from the process's start, so that snapshot holds
exactly what came before ``t0``, the set-up that ``setup_s`` times.  Not
a reader itself.

A series the program never wrote (a program without these spans) reads
None, and so does a slice with no chip's trace: the CPU rehearsal's
set-up is not the cell's.
"""


def at_start(slice_):
    """The snapshot at the window's start, or None."""
    if slice_.get('trace') is None:
        return None
    return slice_.get('snap0')


def histogram_sum(snap, name, absent=None):
    """The sum of one histogram in ``snap``, ``absent`` if there is none."""
    hist = snap.get('histograms', {}).get(name)
    return absent if hist is None else float(hist['sum'])


def histogram_sums(slice_, *names):
    """The sum of the named histograms at the window's start, or None if
    any is absent."""
    snap = at_start(slice_)
    if snap is None:
        return None
    sums = [histogram_sum(snap, name) for name in names]
    return None if None in sums else sum(sums)
