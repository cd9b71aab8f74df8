"""Model FLOPs of a step (``benchmark/flops_kimi_linear.py``: the dense parts
by shape, latent attention at half the square, the delta rule in chunks, the
experts by the assignments the program counted on its held experts) over the
step's device time x the chip's bf16 peak: ``lm_step_mfu_pct``'s arithmetic
on the ``fit_kimi_linear`` driver's ``step_flops``.  Recomputed operations
do not count; idle time between steps is not in the denominator."""
from .lm_step_mfu_pct import read  # noqa: F401
