"""Share of the step's device time under the ``Mamba2Mixer`` operator (its
scopes ``conv``, ``gates``, ``scan`` and ``out_gate``), forward, recomputed
and backward (``benchmark/trace_scopes.py``, the loops' own events taken out
by the driver).  The layer's two projections are ``FullyConnected`` nodes
and are not in it."""


def read(slice_):
    scopes = slice_.get('scopes')
    if not scopes or not scopes['busy_s']:
        return None
    seconds = scopes['by_operator'].get('Mamba2Mixer', 0.0)
    if seconds <= 0:
        return None
    return 100.0 * seconds / scopes['busy_s']
