"""The gated delta rule's share of its roofline: the least time the chip
could take for what the recurrence needs at the configuration's chunk size
(``flops_kimi_linear.kda_scan_flops`` over the bf16 peak or
``kda_scan_bytes`` over the bandwidth, whichever is larger: at heads of 128
and chunks of 64 the bytes bind), every ``KimiDeltaAttention`` layer, over
the device time under the operator's ``scan`` scope.  The count is the
model's work, forward and backward; the time holds the forward pass a
mirror stage computes again as well, so the kernels themselves run at a
larger share than this while the stages are as they are."""
from .. import flops_kimi_linear, peaks


def read(slice_):
    scopes, lm = slice_.get('scopes'), slice_.get('lm')
    if not scopes or not lm or not lm.get('kda') or not slice_.get('steps'):
        return None
    seconds = scopes['by_inner'].get('KimiDeltaAttention/scan', 0.0)
    if seconds <= 0:
        return None
    row = peaks.peaks_for(slice_['device_kind'])
    least = 0.0
    for heads, length, d_k, d_v, chunk in lm['kda']:
        flops = flops_kimi_linear.kda_scan_flops(
            lm['sequences'], heads, length, d_k, d_v, chunk)
        moved = flops_kimi_linear.kda_scan_bytes(
            lm['sequences'], heads, length, d_k, d_v)
        least += max(flops / row['flops_bf16'],
                     moved / row['hbm_bytes_per_s'])
    return 100.0 * least * slice_['steps'] / (seconds * slice_['chips'])
