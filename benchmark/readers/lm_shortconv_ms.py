"""Device time a step under the ``GatedShortConv`` operator: gate, causal
depthwise convolution, gate, forward, recomputed and backward.  Its two
projections are ``FullyConnected`` nodes and are not in it; where XLA fuses
the operator into a projection's fusion, the time goes to that fusion's
root."""


def read(slice_):
    scopes = slice_.get('scopes')
    if not scopes or not slice_.get('steps'):
        return None
    return 1e3 * scopes['by_operator'].get('GatedShortConv', 0.0) / \
        slice_['steps']
