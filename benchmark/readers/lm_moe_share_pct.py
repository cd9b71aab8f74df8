"""Share of the step's device time under the scopes of the ``SparseExperts``
operator, forward, recomputed and backward (``benchmark/trace_scopes.py``)."""


def read(slice_):
    scopes = slice_.get('scopes')
    if not scopes or not scopes['busy_s']:
        return None
    return 100.0 * scopes['by_operator'].get('SparseExperts', 0.0) / \
        scopes['busy_s']
