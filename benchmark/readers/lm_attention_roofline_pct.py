"""Causal attention's share of the chip's peak: ``flops_lm.attention_flops``
(length^2 x head size multiply-adds a sequence and head forward, twice that
backward) over the device time under ``FlashAttention`` x the bf16 peak;
compute binds (``flops_lm.attention_bytes`` is the smaller time).  Attention
lies in no mirror stage, so none of that time is a forward pass computed
again (``trace_scopes``' ``recomputed_by_operator`` has no entry for it)."""
from .. import flops_lm, peaks


def read(slice_):
    scopes, lm = slice_.get('scopes'), slice_.get('lm')
    if not scopes or not lm or not slice_.get('steps'):
        return None
    seconds = scopes['by_operator'].get('FlashAttention', 0.0)
    if seconds <= 0:
        return None
    row = peaks.peaks_for(slice_['device_kind'])
    flops = moved = 0.0
    for heads, kv_heads, length, size in lm['attention']:
        flops += flops_lm.attention_flops(lm['sequences'], heads, length,
                                          size)
        moved += flops_lm.attention_bytes(lm['sequences'], heads, kv_heads,
                                          length, size)
    least = max(flops / row['flops_bf16'], moved / row['hbm_bytes_per_s'])
    return 100.0 * least * slice_['steps'] / (seconds * slice_['chips'])
