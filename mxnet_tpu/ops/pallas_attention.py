"""Fused flash-attention Pallas kernel for TPU.

This is the framework's hand-written hot-op kernel layer — the TPU-native
analogue of the reference's cuDNN fused kernels (the reference reaches
fused attention-era performance through cuDNN primitives such as
``src/operator/cudnn_rnn-inl.h:22-300``; this module plays the same role
for attention on the MXU).

Design
------
Forward is a single ``pl.pallas_call``: the grid walks (batch*heads,
query-block, key-block); an online-softmax accumulator (m, l, acc) lives
in VMEM scratch and persists across the sequential key-block axis, so the
full [T, T] score matrix never materialises in HBM.  Q/K/V blocks stream
HBM->VMEM via BlockSpec pipelining; the two matmuls per block ride the
MXU in fp32 accumulation.

Backward uses the saved per-row log-sum-exp to recompute probabilities
blockwise in plain JAX (`lax.scan` over query blocks, carrying the dK/dV
accumulators) — rematerialisation trades FLOPs for HBM exactly like
``jax.checkpoint``: peak extra memory is one [BH, block_q, Tk] score
block, never the full [Tq, Tk] matrix.

Off-TPU the public entry transparently falls back to a mathematically
identical jnp implementation so the same model code runs in the CPU test
mesh; set ``MXTPU_FORCE_PALLAS_INTERPRET=1`` to exercise the real kernel
through the Pallas interpreter in tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Measured on v5e (T=2048, D=128, causal): 128x128 blocks run at 8.5
# TFLOPs (grid-overhead bound), 512x1024 at ~26, 1024x1024 at ~28 — vs 14
# for XLA's fused softmax-attention.  Large blocks win until VMEM runs out.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024
NEG_INF = -1e30


def _pick_block(t, pref):
    """Largest candidate block size that tiles ``t`` exactly.  The tail
    case must stay a multiple of 8 to satisfy mosaic's (8, 128) sublane
    tiling; anything else routes to the jnp fallback."""
    for b in sorted({pref, 1024, 512, 256, 128}, reverse=True):
        if b <= t and t % b == 0 and b % 8 == 0:
            return b
    return t if (t <= 128 and t % 8 == 0) else None


# Below this K-side sequence length the dense score matrix is cheap
# (f32 [T,T] <= 32 MB at 2048) and XLA's vectorized reference beats the
# Python-emulated interpreter by orders of magnitude; the interpreter's
# O(T^2)-memory savings only pay off past it.  MXTPU_FORCE_PALLAS_INTERPRET
# still forces the kernel at any length.
INTERPRET_MIN_SEQ = 2048


def _mode(seq_len=None):
    # cpu_default='interpret' only at long sequence lengths:
    # attention's reference materializes the full score matrix, so the
    # interpreted kernel is the better CPU path there — but on short and
    # medium sequences the dense jnp expression wins (grid emulation in
    # Python is slow), so those keep 'reference'.
    from .. import config
    cpu_default = 'interpret'
    if seq_len is not None and seq_len < INTERPRET_MIN_SEQ:
        cpu_default = 'reference'
    return config.pallas_mode(cpu_default=cpu_default)


def _use_pallas():
    return _mode() != 'reference'


def _interpret():
    return _mode() == 'interpret'


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, offset,
                block_q, block_k):
    """One (bh, iq, ik) grid step: fold one K/V block into the online
    softmax state held in VMEM scratch."""
    # program_id must be read at the kernel's top level: inside a pl.when
    # body the interpreter cannot substitute it when a grid dim is 1.
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[0].astype(jnp.float32)          # [bq, d]
        k = k_ref[0].astype(jnp.float32)          # [bk, d]
        v = v_ref[0].astype(jnp.float32)          # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        if causal:
            # Bottom-right alignment (row r attends cols <= r + offset,
            # offset = Tk - Tq), matching _ref_attention and _flash_bwd.
            rows = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows + offset >= cols, s, NEG_INF)
        m_prev = m_scr[:]                          # [bq, 1]
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new)                     # [bq, bk]
        corr = jnp.exp(m_prev - m_new)             # [bq, 1]
        l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    if causal:
        # Skip key blocks strictly above the (offset) diagonal.
        needed = ik * block_k <= iq * block_q + (block_q - 1) + offset
        pl.when(needed)(_compute)
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_scr[:]
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        # lse is [1, block_q, 1]: the trailing singleton keeps the block
        # shape legal for mosaic's (8, 128)-tiling rules.
        lse_ref[0] = m_scr[:] + jnp.log(safe_l)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k):
    """q,k,v: [BH, T, D] -> (o [BH, T, D], lse [BH, T])."""
    bh, tq, d = q.shape
    tk = k.shape[1]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    nq = pl.cdiv(tq, block_q)
    nk = pl.cdiv(tk, block_k)

    kwargs = {}
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    scratch = [pltpu.VMEM((block_q, 1), jnp.float32),
               pltpu.VMEM((block_q, 1), jnp.float32),
               pltpu.VMEM((block_q, d), jnp.float32)]
    if not _interpret():
        kwargs['compiler_params'] = pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary'))

    grid = (bh, nq, nk)
    out_shape = [jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
                 jax.ShapeDtypeStruct((bh, tq, 1), jnp.float32)]
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               offset=tk - tq,
                               block_q=block_q, block_k=block_k)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[vmem((1, block_q, d), lambda b, i, j: (b, i, 0)),
                  vmem((1, block_k, d), lambda b, i, j: (b, j, 0)),
                  vmem((1, block_k, d), lambda b, i, j: (b, j, 0))],
        out_specs=[vmem((1, block_q, d), lambda b, i, j: (b, i, 0)),
                   vmem((1, block_q, 1), lambda b, i, j: (b, i, 0))],
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=_interpret(),
        **kwargs,
    )(q, k, v)
    return o, lse[..., 0]


# ---------------------------------------------------------------------------
# reference path + backward (blockwise jnp rematerialisation)
# ---------------------------------------------------------------------------

def _ref_attention(q, k, v, scale, causal):
    s = jnp.einsum('btd,bsd->bts', q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        tq, tk = s.shape[-2:]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum('bts,bsd->btd', p / l, v.astype(jnp.float32))
    lse = (m + jnp.log(l))[..., 0]
    return o.astype(q.dtype), lse


def _flash_bwd(scale, causal, block_q, res, g):
    """Rematerialising backward: ``lax.scan`` over query blocks carrying
    the dK/dV accumulators, so peak extra memory is one
    [BH, block_q, Tk] score block instead of the full [Tq, Tk] matrix."""
    q, k, v, o, lse = res
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    bh, tq, d = qf.shape
    tk = kf.shape[1]
    offset = tk - tq
    delta = jnp.sum(gf * o.astype(jnp.float32), axis=-1)      # [BH, Tq]
    bq = _pick_block(tq, block_q) or tq
    nq = tq // bq

    def to_blocks(x, width):
        return jnp.moveaxis(x.reshape(bh, nq, bq, width), 1, 0)

    cols = jnp.arange(tk)[None, :]

    def step(carry, blk):
        dk_acc, dv_acc = carry
        qb, gb, lseb, deltab, iq = blk
        s = jnp.einsum('btd,bsd->bts', qb, kf) * scale
        if causal:
            rows = iq * bq + jnp.arange(bq)[:, None]
            s = jnp.where(rows + offset >= cols, s, NEG_INF)
        p = jnp.exp(s - lseb[..., None])                       # [BH, bq, Tk]
        dv_acc = dv_acc + jnp.einsum('bts,btd->bsd', p, gb)
        dp = jnp.einsum('btd,bsd->bts', gb, vf)
        ds = p * (dp - deltab[..., None])
        dq_b = jnp.einsum('bts,bsd->btd', ds, kf) * scale
        dk_acc = dk_acc + jnp.einsum('bts,btd->bsd', ds, qb) * scale
        return (dk_acc, dv_acc), dq_b

    zeros = (jnp.zeros_like(kf), jnp.zeros_like(vf))
    blks = (to_blocks(qf, d), to_blocks(gf, d),
            jnp.moveaxis(lse.reshape(bh, nq, bq), 1, 0),
            jnp.moveaxis(delta.reshape(bh, nq, bq), 1, 0),
            jnp.arange(nq))
    (dk, dv), dq_blocks = jax.lax.scan(step, zeros, blks)
    dq = jnp.moveaxis(dq_blocks, 0, 1).reshape(bh, tq, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash3(q, k, v, scale, causal, block_q, block_k):
    o, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k)
    return o


def _flash3_fwd(q, k, v, scale, causal, block_q, block_k):
    o, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k)
    return o, (q, k, v, o, lse)


def _flash3_bwd(scale, causal, block_q, block_k, res, g):
    return _flash_bwd(scale, causal, block_q, res, g)


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """Fused multi-head attention.

    q, k, v: ``[B, H, T, D]`` (or ``[BH, T, D]``).  Returns the attention
    output with the same shape/dtype as ``q``.  Differentiable.
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    squeeze = q.ndim == 4
    if squeeze:
        b, h, t, d = q.shape
        q3 = q.reshape(b * h, t, d)
        k3 = k.reshape(b * h, k.shape[2], d)
        v3 = v.reshape(b * h, v.shape[2], d)
    else:
        q3, k3, v3 = q, k, v

    tq, tk, d = q3.shape[1], k3.shape[1], q3.shape[2]
    bq = _pick_block(tq, block_q)
    bk = _pick_block(tk, block_k)
    aligned = (bq is not None and bk is not None
               and d % 8 == 0 and tq >= 8 and tk >= 8)
    # Causal with tq > tk would leave leading query rows fully masked
    # (undefined attention); route those to the jnp path, whose uniform-
    # weights behavior is at least consistent between forward and grad.
    if causal and tq > tk:
        aligned = False
    if _mode(seq_len=tk) != 'reference' and aligned:
        o3 = _flash3(q3, k3, v3, float(scale), bool(causal),
                     int(bq), int(bk))
    else:
        o3, _ = _ref_attention(q3, k3, v3, float(scale), bool(causal))
    return o3.reshape(q.shape) if squeeze else o3


# ---------------------------------------------------------------------------
# grouped-query attention: fewer key-value heads than query heads
# ---------------------------------------------------------------------------

# Measured on the v5e at 2 x 32 query heads over 8 key-value heads, T 8192,
# D 64, causal, bf16, forward and backward together (PERF.md, PR 28): JAX's
# splash kernel (one key-value head against its group of query heads,
# masked blocks skipped, fused backward) 31.6 ms at blocks of 1024 against
# 38.1 ms with its two-kernel backward, 45.3 ms at 512, and 47.1 ms for
# JAX's flash kernel over repeated keys and values; 2048 does not fit VMEM.
GQA_BLOCK = 1024


# The fused backward kernel writes the queries' gradient once for every
# block of keys and adds the parts up afterwards: sequences x heads x
# (T / block) x T x D values.  At 2 x 32 heads of 64 that is 0.5e9 B and
# worth its time (above); at 2 x 32 heads of 192 it is 1.6e9 B laid out as
# 2.1e9, more than the whole layer keeps otherwise, so past this size the
# queries' gradient gets its own kernel.
FUSED_BWD_PARTS_MAX_BYTES = 1 << 30


@functools.lru_cache(maxsize=None)
def _splash_kernel(t, group, causal, block, fused_bwd=True):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel, splash_attention_mask as masks)
    mask = masks.CausalMask((t, t)) if causal else masks.FullMask((t, t))
    sizes = kernel.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=None if fused_bwd else block,
        block_kv_dq=None if fused_bwd else block,
        use_fused_bwd_kernel=fused_bwd)
    # made once and kept: its mask tables must be arrays, not the tracers
    # of whichever trace asked first
    with jax.ensure_compile_time_eval():
        return kernel.make_splash_mqa_single_device(
            mask=masks.MultiHeadMask([mask] * group), block_sizes=sizes)


def gqa_attention(q, k, v, causal=False, scale=None):
    """Attention of ``q`` [B, H, T, D] over ``k`` [B, KV, T, D] and ``v``
    [B, KV, T, Dv], each key-value head serving H / KV consecutive query
    heads; the values may be narrower or wider than the keys (latent
    attention: keys of 192, values of 128), and the output is [B, H, T, Dv].
    On the TPU the splash kernel, with its own backward; elsewhere, and at
    lengths it does not tile, the jnp expression over repeated keys and
    values."""
    b, h, t, d = q.shape
    kv, dv = k.shape[1], v.shape[-1]
    if h % kv or v.shape[1] != kv or k.shape[-1] != d or \
            v.shape[2] != k.shape[2]:
        raise ValueError('queries %s, keys %s and values %s do not go '
                         'together' % (q.shape, k.shape, v.shape))
    group = h // kv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    block = next((c for c in (GQA_BLOCK, 512, 256, 128) if t % c == 0), None)
    if _mode(seq_len=t) == 'kernel' and block and k.shape[2] == t:
        parts = b * h * (t // block) * t * d * q.dtype.itemsize
        attend = _splash_kernel(t, group, bool(causal), block,
                                parts <= FUSED_BWD_PARTS_MAX_BYTES)
        # the kernel applies no scale of its own
        grouped = (q * jnp.asarray(scale, q.dtype)).reshape(b, kv, group,
                                                            t, d)
        return jax.vmap(jax.vmap(attend))(grouped, k, v) \
            .reshape(b, h, t, dv)
    k3 = jnp.repeat(k, group, axis=1).reshape(b * h, k.shape[2], d)
    v3 = jnp.repeat(v, group, axis=1).reshape(b * h, v.shape[2], dv)
    o3, _ = _ref_attention(q.reshape(b * h, t, d), k3, v3, float(scale),
                           bool(causal))
    return o3.reshape(b, h, t, dv)
