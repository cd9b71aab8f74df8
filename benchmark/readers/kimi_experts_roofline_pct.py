"""The grouped products' share of the chip's peak in ``kimi_linear_fit_8k``:
``lm_experts_roofline_pct``'s reading (the counted assignments' work over
the device time under ``SparseExperts``' ``experts`` scope) on the
``fit_kimi_linear`` driver's slice, whose ``lm`` entry carries the same
keys."""
from .lm_experts_roofline_pct import read  # noqa: F401
