"""Latent attention's share of the chip's peak:
``flops_kimi_linear.attention_flops`` (the causal half of the square, keys of
192 and values of 128 a head: length^2 / 2 x 320 multiply-adds a sequence
and head forward, twice that backward) over the device time under
``FlashAttention`` x the bf16 peak; compute binds
(``flops_kimi_linear.attention_bytes`` is the smaller time).  Latent
attention lies in no mirror stage, so none of that time is a forward pass
computed again."""
from .. import flops_kimi_linear, peaks


def read(slice_):
    scopes, lm = slice_.get('scopes'), slice_.get('lm')
    if not scopes or not lm or not slice_.get('steps'):
        return None
    seconds = scopes['by_operator'].get('FlashAttention', 0.0)
    if seconds <= 0:
        return None
    row = peaks.peaks_for(slice_['device_kind'])
    flops = moved = 0.0
    for heads, kv_heads, length, d_k, d_v in lm['attention']:
        flops += flops_kimi_linear.attention_flops(
            lm['sequences'], heads, length, d_k, d_v)
        moved += flops_kimi_linear.attention_bytes(
            lm['sequences'], heads, kv_heads, length, d_k, d_v)
    least = max(flops / row['flops_bf16'], moved / row['hbm_bytes_per_s'])
    return 100.0 * least * slice_['steps'] / (seconds * slice_['chips'])
