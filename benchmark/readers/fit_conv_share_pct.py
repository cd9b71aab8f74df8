"""Share of the device's busy time spent in convolution and
matrix-product fusions (``trace_reduce.is_convolution``)."""


def read(slice_):
    trace = slice_.get('trace')
    if not trace or not trace['busy_s']:
        return None
    return 100.0 * trace['conv_s'] / trace['busy_s']
