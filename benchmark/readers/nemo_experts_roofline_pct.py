"""The ungated grouped products' share of the chip's peak: the work the held
experts' assignments need (``flops_nemotron_h.experts_flops``: two products
an assignment forward, twice that backward; the same whatever implements
it) or, if larger, the time their bytes need
(``flops_nemotron_h.experts_bytes``), over the device time under
``SparseExperts``' ``experts`` scope.  The model's work over the time the
program takes for it: a forward pass computed again inside that time is not
counted."""
from .. import flops_nemotron_h, peaks


def read(slice_):
    scopes, lm = slice_.get('scopes'), slice_.get('lm')
    if not scopes or not lm or not slice_.get('steps'):
        return None
    seconds = scopes['by_inner'].get('SparseExperts/experts', 0.0)
    if seconds <= 0:
        return None
    row = peaks.peaks_for(slice_['device_kind'])
    shape = (lm['expert_width_in'], lm['expert_width'],
             lm['expert_matrices'])
    flops = flops_nemotron_h.experts_flops(lm['assignments_held_per_step'],
                                           *shape)
    moved = flops_nemotron_h.experts_bytes(lm['assignments_held_per_step'],
                                           lm['experts_held_total'], *shape)
    least = max(flops / row['flops_bf16'], moved / row['hbm_bytes_per_s'])
    return 100.0 * least * slice_['steps'] / (seconds * slice_['chips'])
