"""The state-space recurrence's share of its roofline: the least time the
chip could take for what the recurrence needs at the configuration's chunk
size (``flops_nemotron_h.ssd_scan_flops`` over the bf16 peak or
``ssd_scan_bytes`` over the bandwidth, whichever is larger: at 16 heads of
64 on a state of 128 and chunks of 128 the bytes bind), every
``Mamba2Mixer`` layer, at the heads and groups the layer holds, over the
device time under the operator's ``scan`` scope.  The count is the model's
work, forward and backward; the time holds the forward pass a mirror stage
and the segments' backward pass compute again as well."""
from .. import flops_nemotron_h, peaks


def read(slice_):
    scopes, lm = slice_.get('scopes'), slice_.get('lm')
    if not scopes or not lm or not lm.get('ssm') or not slice_.get('steps'):
        return None
    seconds = scopes['by_inner'].get('Mamba2Mixer/scan', 0.0)
    if seconds <= 0:
        return None
    row = peaks.peaks_for(slice_['device_kind'])
    least = 0.0
    for heads, groups, length, size, state, chunk in lm['ssm']:
        flops = flops_nemotron_h.ssd_scan_flops(
            lm['sequences'], heads, groups, length, size, state, chunk)
        moved = flops_nemotron_h.ssd_scan_bytes(
            lm['sequences'], heads, groups, length, size, state)
        least += max(flops / row['flops_bf16'],
                     moved / row['hbm_bytes_per_s'])
    return 100.0 * least * slice_['steps'] / (seconds * slice_['chips'])
