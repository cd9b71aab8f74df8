"""Share of the step's device time under the scopes of the ``SparseExperts``
operator and under the latent's two projections (the ``FullyConnected``
nodes round it, named in the slice's ``lm`` entry), forward, recomputed and
backward: what LatentMoE's routed part costs.  The shared expert's two
``FullyConnected`` are not in it."""


def read(slice_):
    scopes, lm = slice_.get('scopes'), slice_.get('lm')
    if not scopes or not lm or not scopes['busy_s']:
        return None
    seconds = scopes['by_operator'].get('SparseExperts', 0.0)
    if seconds <= 0:
        return None
    seconds += sum(scopes['by_node'].get('FullyConnected/' + name, 0.0)
                   for name in lm.get('latent_projections', ()))
    return 100.0 * seconds / scopes['busy_s']
