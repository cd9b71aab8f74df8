"""The part of the collectives' device time during which no other op
runs on that chip, per step, mean over the chips."""


def read(slice_):
    trace = slice_.get('trace')
    if not trace or not slice_.get('steps') or trace['chips'] < 2:
        return None
    return 1e3 * trace['collective_exposed_s'] / slice_['steps']
