"""ZeRO-style sharded data parallelism (optimizer-state + update
sharding over the dp axis).

The reference's data-parallel story keeps a full copy of every weight,
gradient and optimizer slot on each device and all-reduces gradients
(``src/kvstore/comm.h`` CommDevice).  On a TPU mesh the idiomatic
upgrade is the scaling-book / ZeRO recipe: ``psum_scatter`` the
gradients so each device owns 1/N of every parameter's update,
optimizer state lives only on the owning shard, and the updated shards
are ``all_gather``-ed back into the replicated parameters — per step
traffic is the same as one all-reduce (scatter + gather), while
optimizer memory drops by N.

All parameters ride ONE fused buffer: each param is padded to N·chunk,
laid out as an (N, chunk) block, and the blocks are concatenated along
the chunk axis — so the whole model costs exactly two collective
launches per step (one psum_scatter, one all_gather) regardless of how
many tensors it has (the same batching argument as
``collectives.allreduce_hosts_batch`` for the kvstore push path).

Used inside ``shard_map`` over the dp axis; composes with the tp/sp
legs the same way plain psum data parallelism does (it replaces only
the gradient-reduce + update).

Role equivalents in the reference: the kvstore updater-on-server mode
(``kvstore_dist_server.h:136-219``) also keeps ONE authoritative copy
of each weight and ships deltas — ZeRO is that idea executed on-mesh
with collectives instead of a parameter server.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _layout(params, n_shards):
    """Deterministic fused-buffer layout: sorted names, per-param
    shard-chunk sizes and offsets into the (n, C) concatenation."""
    names = sorted(params)
    chunks = {}
    offsets = {}
    off = 0
    for k in names:
        size = int(np.prod(params[k].shape))
        chunk = -(-size // n_shards)  # ceil div
        chunks[k] = chunk
        offsets[k] = off
        off += chunk
    return names, chunks, offsets, off


def zero_state_size(params, n_shards):
    """Per-device optimizer slot count: one f32 momentum lane per owned
    parameter element (the fused C of the layout)."""
    return _layout(params, n_shards)[3]


def zero_init(params, n_shards):
    """Per-device momentum shard — a single fused (C,) vector (call
    INSIDE shard_map, or broadcast the zeros: identical at init)."""
    return jnp.zeros((zero_state_size(params, n_shards),), jnp.float32)


def _to_blocks(tree, names, chunks, n_shards, dtype=jnp.float32):
    rows = []
    for k in names:
        flat = tree[k].astype(dtype).reshape(-1)
        pad = chunks[k] * n_shards - flat.shape[0]
        rows.append(jnp.pad(flat, (0, pad)).reshape(n_shards,
                                                    chunks[k]))
    return jnp.concatenate(rows, axis=1)  # (n, C)


def make_zero_sgd_momentum(axis_name, n_shards, lr=0.05, momentum=0.9,
                           wd=1e-4, rescale_grad=1.0):
    """Sharded SGD-with-momentum update; call INSIDE shard_map.

    Args:
      params    — replicated full parameters (identical on every
                  device along ``axis_name``)
      grads     — device-local UNREDUCED gradients (pytree like params)
      mom_shard — this device's fused (C,) momentum vector

    Returns (new_params, new_mom_shard); new_params are again
    replicated (all-gathered).
    """
    def update(params, grads, mom_shard):
        names, chunks, offsets, _ = _layout(params, n_shards)
        idx = jax.lax.axis_index(axis_name)

        # sum across dp + keep this device's 1/N of every param:
        # ONE reduce-scatter for the whole model
        g_blocks = _to_blocks(grads, names, chunks, n_shards)
        g_shard = jax.lax.psum_scatter(g_blocks.reshape(-1), axis_name,
                                       scatter_dimension=0, tiled=True)
        p_blocks = _to_blocks(params, names, chunks, n_shards)
        p_shard = jax.lax.dynamic_index_in_dim(p_blocks, idx, 0,
                                               keepdims=False)

        # lr-folded buffer (m = mu*m - lr*g), the same formulation as
        # make_sgd_momentum / the reference sgd_mom_update — optimizer
        # state stays interchangeable with the non-ZeRO path and the
        # trajectory tracks lr changes mid-training
        mom = momentum * mom_shard \
            - lr * (g_shard * rescale_grad + wd * p_shard)
        p_new = p_shard + mom

        # ONE all-gather rebuilds the replicated params
        full = jax.lax.all_gather(p_new, axis_name,
                                  tiled=True).reshape(n_shards, -1)
        new_params = {}
        for k in names:
            p = params[k]
            size = int(np.prod(p.shape))
            seg = full[:, offsets[k]:offsets[k] + chunks[k]]
            new_params[k] = seg.reshape(-1)[:size].reshape(p.shape) \
                .astype(p.dtype)
        return new_params, mom

    return update


def zero_partition_spec(shape, mesh, dp_axis='dp', base=None):
    """ZeRO-style PartitionSpec for ONE optimizer-state leaf under the
    NamedSharding product path (``Module.fit(mesh=...)``, docs/
    parallel.md).

    The shard_map legs above fuse all state into one (N, C) buffer;
    the jit/GSPMD path instead keeps every leaf in its natural shape
    and SHARDS it over the dp axis — starting from ``base`` (the
    owning parameter's tp spec, so tensor- and optimizer-sharding
    compose) and adding ``dp_axis`` on the largest still-unsharded
    dp-divisible dim.  Leaves where no dim fits stay on ``base``
    (replicated over dp): the policy degrades per-tensor, never fails
    a model.

    Declaring the state's in/out shardings this way makes XLA's
    partitioner emit exactly the ZeRO schedule: gradients reduce-
    scatter into the owning dp shard, the update runs shard-local, and
    the all-gather happens on the (replicated-spec) parameters — same
    two collectives as :func:`make_zero_sgd_momentum`, with optimizer
    memory per device divided by dp for every sharded leaf.
    """
    from jax.sharding import PartitionSpec as P
    ndp = int(mesh.shape.get(dp_axis, 1))
    spec = zero_spec_for(shape, ndp, base=base, dp_axis=dp_axis)
    return P(*spec) if spec else P()


def zero_spec_for(shape, ndp, base=None, dp_axis='dp'):
    """Mesh-free core of :func:`zero_partition_spec`: the per-dim axis
    tuple (empty = replicated) a leaf of ``shape`` gets when ZeRO-
    sharded over ``ndp`` data-parallel shards on top of ``base`` (the
    owning parameter's tp spec).  Shared with the sharding inspector's
    shapes mode (``mesh.records_for_shapes`` / tools/
    explain_sharding.py), so the inspector and the live placement
    cannot drift."""
    from .mesh import _pick_shard_dim
    base_spec = tuple(base) if base is not None else ()
    base_spec = base_spec + (None,) * (len(shape) - len(base_spec))
    taken = tuple(i for i, s in enumerate(base_spec) if s is not None)
    # the SAME selection rule tp placement uses (mesh._pick_shard_dim)
    # so the two policies cannot drift apart
    best = _pick_shard_dim(shape, int(ndp), taken=taken)
    if best is None:
        return base_spec if any(s is not None for s in base_spec) else ()
    spec = list(base_spec)
    spec[best] = dp_axis
    return tuple(spec)


def zero_opt_init(params, n_shards):
    """GLOBAL optimizer state for :func:`make_zero_train_step`: an
    (n_shards, C) zero buffer to be placed sharded over the dp axis
    (each row is one device's fused momentum vector)."""
    return jnp.zeros((n_shards, zero_state_size(params, n_shards)),
                     jnp.float32)


def make_zero_train_step(symbol, mesh, axis_name, lr=0.05,
                         momentum=0.9, wd=1e-4, rescale_grad=1.0,
                         compute_dtype=None, donate=True):
    """Fused fwd/bwd/ZeRO-update step over a dp mesh axis.

    Returns ``step(params, aux, opt_state, batch, rng) -> (outputs,
    params, aux, opt_state)`` — the same contract as
    ``train_step.make_train_step`` but executed under ``shard_map``:
    the batch arrives sharded on ``axis_name``, gradients are
    psum_scattered so each device updates 1/N of every parameter with
    shard-local optimizer state (``zero_opt_init``), and updated
    params are all_gathered back to replicated.

    BatchNorm batch statistics are shard-local (each device normalizes
    with its own batch shard's stats) — the reference's multi-GPU
    data-parallel semantics (each GPU's executor computes its own BN
    stats; ``src/operator/batch_norm-inl.h`` has no cross-device
    reduction).  Moving-average aux states are pmean'd so replicas
    stay identical.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from .train_step import make_fit_step, _PlainUpdate

    # loss normalization must be global: a shard-local 'batch'/'valid'
    # divisor would make the psum_scattered gradient N times larger
    # than the same symbol through make_train_step on the full batch.
    # Use normalization='null' + rescale_grad=1/global_batch instead.
    for node in symbol.topo_nodes():
        if node.is_variable:
            continue
        norm = node.attrs.get('normalization')
        if node.op.endswith('Output') and norm in ('batch', 'valid'):
            raise ValueError(
                "make_zero_train_step: %s normalization=%r divides by "
                "the SHARD-local batch under shard_map; use "
                "normalization='null' with rescale_grad=1/global_batch"
                % (node.op, norm))

    n_shards = mesh.shape[axis_name]
    zupd = make_zero_sgd_momentum(axis_name, n_shards, lr=lr,
                                  momentum=momentum, wd=wd,
                                  rescale_grad=rescale_grad)
    raw = make_fit_step(symbol, _PlainUpdate(zupd), data_names=(),
                        compute_dtype=compute_dtype, _raw=True)

    def local_step(params, aux, mom_row, batch, rng):
        # per-device dropout/noise streams
        rng = jax.random.fold_in(rng, jax.lax.axis_index(axis_name))
        mom = mom_row.reshape(-1)          # (1, C) block -> (C,)
        outs, new_p, new_aux, new_mom = raw(
            params, {}, aux, mom, batch, jnp.float32(0.0), rng)
        new_aux = {k: jax.lax.pmean(v, axis_name)
                   for k, v in new_aux.items()}
        return outs, new_p, new_aux, new_mom.reshape(1, -1)

    sharded = shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), P(), P(axis_name), P(axis_name), P()),
        out_specs=(P(axis_name), P(), P(), P(axis_name)),
        check_vma=False)
    if donate:
        # in-place update semantics (reference discipline, same as
        # make_train_step): old params/aux/opt buffers are donated
        return jax.jit(sharded, donate_argnums=(0, 1, 2))
    return jax.jit(sharded)
