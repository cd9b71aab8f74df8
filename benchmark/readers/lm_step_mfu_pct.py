"""Model FLOPs of a step (``benchmark/flops_lm.py``: the dense parts by
shape, the experts by the assignments the program counted on its held
experts) over the step's device time x the chip's bf16 peak: the arithmetic
of ``fit_step_flops_pct`` on this driver's ``step_flops``.  Recomputed
operations do not count; idle time between steps is not in the
denominator."""
from . import fit_step_flops_pct


def read(slice_):
    if not slice_.get('step_flops'):
        return None
    return fit_step_flops_pct.read(slice_)
