"""Model FLOPs of a step (``benchmark/flops_nemotron_h.py``: the dense parts
by shape, attention at half the square, the state-space recurrence in chunks
at the heads and groups held, the experts by the assignments the program
counted on its held experts) over the step's device time x the chip's bf16
peak: ``lm_step_mfu_pct``'s arithmetic on the ``fit_nemotron_h`` driver's
``step_flops``.  Recomputed operations do not count; idle time between steps
is not in the denominator."""
from .lm_step_mfu_pct import read  # noqa: F401
