"""Ring attention — sequence/context parallelism over the ICI ring.

Beyond-reference capability (SURVEY.md §5 long-context entry): the
reference's longest-context tools were bucketing + fused cuDNN RNN +
layer placement; modern long-context training needs the sequence axis
sharded across chips.  This module implements blockwise ring attention
(Liu et al., "Ring Attention with Blockwise Transformers", 2023-style
algorithm): each chip holds a T/N slice of Q/K/V; K,V blocks rotate
around the mesh axis via ``ppermute`` while each chip accumulates its
queries' attention with an online-softmax (log-sum-exp) update, so peak
memory is O(T/N) and the K/V transfer overlaps the per-block matmuls on
the MXU.

Use inside ``shard_map`` over a mesh with a ``seq`` axis; or call
:func:`make_ring_attention` for a ready-made jitted sharded function.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _block_update(q, k, v, m, l, o, mask=None, scale=1.0):
    """Online-softmax accumulation of one K/V block.

    q: [B, H, Tq, D]; k,v: [B, H, Tk, D]; m,l: [B, H, Tq]; o like q.
    """
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k) * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # guard fully-masked rows (m_new == -inf)
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - safe_m[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
    l_new = l * corr + jnp.sum(p, axis=-1)
    o_new = o * corr[..., None] + jnp.einsum('bhqk,bhkd->bhqd', p, v)
    return m_new, l_new, o_new


def ring_attention(q, k, v, axis_name, causal=False):
    """Blockwise attention with K/V rotating around ``axis_name``.

    Per-shard shapes: q,k,v ``[B, H, T_local, D]``; returns ``[B,H,T_local,D]``.
    Must run inside ``shard_map``/``pmap`` with ``axis_name`` bound.
    """
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    scale = 1.0 / np.sqrt(q.shape[-1])
    t_local = q.shape[2]

    # online-softmax state accumulates in f32 whatever the input
    # dtype (bf16 exp/renormalization chains lose the tail); the
    # result is cast back at the end
    out_dtype = q.dtype
    q = q.astype(jnp.float32)
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    m0 = jnp.full(q.shape[:2] + (t_local,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros(q.shape[:2] + (t_local,), jnp.float32)
    o0 = jnp.zeros_like(q)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(step, carry):
        k_blk, v_blk, m, l, o = carry
        # source shard of the current block
        src = (my_idx - step) % n
        if causal:
            q_pos = my_idx * t_local + jnp.arange(t_local)
            k_pos = src * t_local + jnp.arange(t_local)
            mask = q_pos[:, None] >= k_pos[None, :]
            mask = mask[None, None]
        else:
            mask = None
        m, l, o = _block_update(q, k_blk, v_blk, m, l, o, mask, scale)
        # rotate K/V to the next chip; on the last step the rotation is
        # still issued (uniform loop body keeps XLA pipelining simple)
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, m, l, o

    _, _, m, l, o = jax.lax.fori_loop(0, n, body, (k, v, m0, l0, o0))
    l = jnp.maximum(l, 1e-20)
    return (o / l[..., None]).astype(out_dtype)


def full_attention(q, k, v, causal=False):
    """Single-device attention, [B, H, T, D].

    Routes to the fused flash-attention Pallas kernel
    (:mod:`mxnet_tpu.ops.pallas_attention`) on TPU; falls back to the
    plain jnp softmax-attention elsewhere (the kernel module makes the
    same decision internally, including alignment checks).
    """
    from ..ops.pallas_attention import flash_attention
    return flash_attention(q, k, v, causal=causal)


def make_ring_attention(mesh: Mesh, seq_axis: str = 'seq', causal=False):
    """Jitted sharded attention: inputs [B, H, T, D] sharded on T."""
    from jax import shard_map

    spec = P(None, None, seq_axis, None)

    @functools.partial(jax.jit)
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    def attn(q, k, v):
        return ring_attention(q, k, v, seq_axis, causal=causal)

    return attn


def make_ulysses_attention(mesh: Mesh, seq_axis: str = 'seq', causal=False):
    """DeepSpeed-Ulysses-style context parallelism: all-to-all swaps the
    sharded axis from sequence to heads, runs full attention locally on
    H/N heads, and swaps back.  Complementary to ring attention — better
    when H >= N and the all-to-all fits ICI."""
    from jax import shard_map

    spec = P(None, None, seq_axis, None)

    @functools.partial(jax.jit)
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    def attn(q, k, v):
        def seq_to_heads(x):
            # [B, H, T/N, D] -> all_to_all -> [B, H/N, T, D]
            return jax.lax.all_to_all(x, seq_axis, split_axis=1,
                                      concat_axis=2, tiled=True)

        def heads_to_seq(x):
            return jax.lax.all_to_all(x, seq_axis, split_axis=2,
                                      concat_axis=1, tiled=True)

        qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
        oh = full_attention(qh, kh, vh, causal=causal)
        return heads_to_seq(oh)

    return attn
