"""The grouped products' share of the chip's peak: the work the held
experts' assignments need (``flops_lm.experts_flops``: three products an
assignment forward, twice that backward; the same whatever implements it)
over the device time under ``SparseExperts``' ``experts`` scope x the bf16
peak.  The products are bound by compute (``flops_lm.experts_bytes`` over
the chip's bandwidth is the smaller time), so the share is of FLOPs.  It is
the model's work over the time the program takes for it: a mirror stage
runs the forward products a second time inside that time, which the count
leaves out as it leaves out all recomputation (twelve products a layer run
for the nine counted; ``trace_scopes``' ``kernel_instructions`` and the
driver's log say how many), so the kernels themselves run at 12 / 9 of
this share while the stages are as they are."""
from .. import flops_lm, peaks


def read(slice_):
    scopes, lm = slice_.get('scopes'), slice_.get('lm')
    if not scopes or not lm or not slice_.get('steps'):
        return None
    seconds = scopes['by_inner'].get('SparseExperts/experts', 0.0)
    if seconds <= 0:
        return None
    row = peaks.peaks_for(slice_['device_kind'])
    flops = flops_lm.experts_flops(lm['assignments_held_per_step'],
                                   lm['expert_width_in'], lm['expert_width'])
    moved = flops_lm.experts_bytes(lm['assignments_held_per_step'],
                                   lm['experts_held_total'],
                                   lm['expert_width_in'], lm['expert_width'])
    least = max(flops / row['flops_bf16'], moved / row['hbm_bytes_per_s'])
    return 100.0 * least * slice_['steps'] / (seconds * slice_['chips'])
