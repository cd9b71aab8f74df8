"""Mixture-of-Experts with expert parallelism over a device mesh: the
top-1, fixed-capacity form (tokens over an expert's capacity are DROPPED,
dispatch is a dense ``(T, E, C)`` one-hot), as plain JAX functions that no
Symbol or ``Module`` reaches.  What trains through ``Module.fit`` is the
``SparseExperts`` operator (``ops/lm.py``: top-k, no token dropped, grouped
products over sorted assignments), which computes one device's share and
has no exchange yet; this module keeps the one thing that operator lacks,
the ``all_to_all`` exchange over an ``expert`` mesh axis (ROADMAP.md, Reach
item 1), and its dry run in ``__graft_entry__.dryrun_multichip``.

An extension beyond the 2017-era reference (SURVEY.md §2.4 lists expert
parallelism as absent there), included because the TPU-native framework
treats distributed execution as first-class: experts shard over an
``expert`` mesh axis, tokens are exchanged with ``all_to_all`` over ICI
(the GShard/Switch dispatch pattern), and the load-balancing auxiliary
loss keeps routing uniform.

All shapes are static: every expert processes a fixed ``capacity`` of
token slots per shard (overflow tokens are dropped, underflow slots are
zero-padded), which is what lets XLA compile one fused program instead
of data-dependent gathers.

Layout inside ``shard_map`` (per expert-shard):
    x: (tokens_local, d_model)  — token-sharded input
    experts' weights: (experts_local, d_model, d_ff) — expert-sharded
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def top1_gating(logits, capacity):
    """Switch-style top-1 routing.

    logits: (T, E).  Returns (dispatch (T, E, C) one-hot, combine
    (T, E, C) weights, aux_loss scalar).
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)                    # (T,)
    onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)  # (T, E)

    # position of each token within its expert's queue
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0        # (T, E)
    pos_in_expert = jnp.sum(pos * onehot, axis=1)          # (T,)
    keep = (pos_in_expert < capacity) & (pos_in_expert >= 0)

    gate = jnp.sum(probs * onehot, axis=1) * keep          # (T,)
    slot = jax.nn.one_hot(pos_in_expert.astype(jnp.int32), capacity,
                          dtype=jnp.float32)               # (T, C)
    dispatch = onehot[:, :, None] * slot[:, None, :] \
        * keep[:, None, None]
    combine = dispatch * gate[:, None, None]

    # GShard load-balancing loss: E * sum_e fraction_e * mean_prob_e
    frac = jnp.mean(onehot, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux


def moe_ffn_local(x, gate_w, up_w, down_w, capacity, axis_name=None):
    """One MoE feed-forward layer; call inside shard_map with the
    ``expert`` axis bound (axis_name) for expert parallelism, or with
    axis_name=None for single-device execution.

    x: (T, D); gate_w: (D, E_total); up_w: (E_local, D, F);
    down_w: (E_local, F, D).
    """
    t, d = x.shape
    e_local = up_w.shape[0]
    n_shards = 1 if axis_name is None else jax.lax.psum(1, axis_name)
    e_total = e_local * n_shards

    logits = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    dispatch, combine, aux = top1_gating(logits, capacity)

    # (T, E, C) x (T, D) -> (E, C, D): expert-major token slots
    slots = jnp.einsum('tec,td->ecd', dispatch, x.astype(jnp.float32))
    if axis_name is not None:
        # exchange token slots so each shard holds ALL tokens routed to
        # its local experts: (E_total, C, D) -> (n, E_local, C, D) over
        # the expert axis, then concat the per-source-shard capacity
        slots = slots.reshape(n_shards, e_local, capacity, d)
        slots = jax.lax.all_to_all(slots, axis_name, split_axis=0,
                                   concat_axis=1, tiled=False)
        # (E_local, n*C, D)
        slots = slots.reshape(e_local, n_shards * capacity, d)

    h = jnp.einsum('ecd,edf->ecf', slots.astype(x.dtype), up_w)
    h = jax.nn.relu(h)
    out = jnp.einsum('ecf,efd->ecd', h, down_w)

    if axis_name is not None:
        # (E_local, n, C, D): chunk j goes back to source shard j; the
        # received pieces stack shard-major at axis 0, which is exactly
        # the global expert order (experts are contiguous per shard)
        out = out.reshape(e_local, n_shards, capacity, d)
        out = jax.lax.all_to_all(out, axis_name, split_axis=1,
                                 concat_axis=0, tiled=False)
        out = out.reshape(e_total, capacity, d)

    y = jnp.einsum('tec,ecd->td', combine, out.astype(jnp.float32))
    return y.astype(x.dtype), aux


def make_moe_ffn(mesh: Mesh, expert_axis: str = 'expert',
                 capacity_factor: float = 1.25):
    """Expert-parallel MoE layer jitted over ``mesh``.

    Returns ``fn(x, gate_w, up_w, down_w) -> (y, aux_loss)``.
    ``x`` is TOKEN-sharded over ``expert_axis`` (the GShard layout:
    the data and expert dimensions ride the same mesh axis);
    ``up_w``/``down_w`` lead with the FULL expert dimension and shard
    over the same axis; the gate is replicated.  Tokens travel to their
    experts and back via the two ``all_to_all`` exchanges — the ICI
    dispatch pattern.
    """
    from jax import shard_map
    n = mesh.shape[expert_axis]

    def fn(x, gate_w, up_w, down_w):
        t_local = x.shape[0] // n
        e_total = up_w.shape[0]
        # per-source-shard slots per expert (GShard sizing); each expert
        # receives n*capacity slots in total across source shards
        capacity = max(1, int(capacity_factor * t_local / e_total))

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(expert_axis), P(), P(expert_axis),
                      P(expert_axis)),
            out_specs=(P(expert_axis), P()))
        def inner(xs, gw, uw, dw):
            y, aux = moe_ffn_local(xs, gw, uw, dw, capacity,
                                   axis_name=expert_axis)
            return y, jax.lax.pmean(aux, expert_axis)
        return inner(x, gate_w, up_w, down_w)
    return fn


def moe_reference(x, gate_w, up_w, down_w, capacity):
    """Dense single-device reference for testing: identical math,
    no collectives."""
    return moe_ffn_local(x, gate_w, up_w, down_w, capacity,
                         axis_name=None)
