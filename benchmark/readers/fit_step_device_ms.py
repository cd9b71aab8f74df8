"""Device time of one fit step: the union of the intervals in which an
op ran on a chip over the traced slice, per step, mean over the chips."""


def read(slice_):
    trace = slice_.get('trace')
    if not trace or not slice_.get('steps'):
        return None
    return 1e3 * trace['busy_s'] / slice_['steps']
