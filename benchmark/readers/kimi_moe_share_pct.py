"""Share of the step's device time under the scopes of the ``SparseExperts``
operator, forward, recomputed and backward: ``lm_moe_share_pct``'s reading
under the ``fit_kimi_linear`` driver's name.  The shared expert is three
``FullyConnected`` nodes and is not in it."""
from .lm_moe_share_pct import read  # noqa: F401
