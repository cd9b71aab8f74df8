"""Symbol-level sequence (context) parallelism — the product surface
over :mod:`parallel.ring`.

``make_sp_train_step(symbol, mesh)`` compiles an MXNet-style symbol
(e.g. ``models.get_symbol('transformer_lm')``) into ONE fused
fwd+bwd+optimizer program running under ``shard_map`` with the
SEQUENCE dimension sharded over a mesh axis: every ``FlashAttention``
node lowers to :func:`parallel.ring.ring_attention` (K/V blocks
rotating over ICI, online-softmax accumulation), token-wise ops run
shard-local, and parameter gradients are ``psum``-reduced across the
sequence shards.  This is how a Module-API user trains long-context
models that do not fit one chip's sequence budget — without writing
any JAX.

The reference had no sequence parallelism (2017-era, SURVEY.md §5
long-context gap); this extends its Module/symbol idiom to the ring
recipe.
"""
from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_TLS = threading.local()


def current_sp_axis():
    """The sequence-parallel mesh axis active during graph tracing, or
    None.  ``ops.nn._flash_attention_apply`` dispatches to ring (or
    Ulysses) attention when set."""
    return getattr(_TLS, 'axis', None)


def current_sp_mode():
    """'ring' (K/V rotation) or 'ulysses' (all-to-all head swap)."""
    return getattr(_TLS, 'mode', 'ring')


@contextlib.contextmanager
def sp_scope(axis, mode='ring'):
    prev = getattr(_TLS, 'axis', None)
    prev_mode = getattr(_TLS, 'mode', 'ring')
    _TLS.axis = axis
    _TLS.mode = mode
    try:
        yield
    finally:
        _TLS.axis = prev
        _TLS.mode = prev_mode


def make_sp_train_step(symbol, mesh: Mesh, optimizer_update,
                       seq_axis='seq', seq_param_names=(),
                       batch_specs=None, compute_dtype=None,
                       data_names=(), attn_mode='ring'):
    """Build ``step(params, opt_state, batch, rng) ->
    (outputs, params, opt_state)`` with the sequence dim sharded.

    Args:
      symbol: loss-bearing symbol; its ``FlashAttention`` nodes become
        ring attention over ``seq_axis``.
      optimizer_update: functional ``(params, grads, state) ->
        (new_params, new_state)`` (e.g. ``make_sgd_momentum``).
      seq_param_names: parameters sharded along their FIRST axis with
        the sequence (e.g. a learned positional-embedding table);
        their gradients stay shard-local.  All other parameters are
        replicated and their gradients psum over ``seq_axis``.
      batch_specs: {name: PartitionSpec} for batch entries; default
        shards dim 1 of every entry (the (N, T) LM layout).
      compute_dtype: optional bf16 compute cast, labels excluded.
      attn_mode: 'ring' (K/V rotation — any head count) or 'ulysses'
        (all-to-all head swap — needs heads %% shards == 0; better
        when the all-to-all fits ICI).

    The batch's sequence length must divide by the mesh axis size.

    CONTRACT — build the symbol at the SHARD-LOCAL sequence length
    (``global_T // mesh.shape[seq_axis]``): under shard_map each
    device runs the graph on its own sequence slice, so every static
    shape baked into the symbol (Reshape targets, positional tables)
    is the local one.  Ring attention still applies the GLOBAL causal
    mask (it offsets by the shard index internally).  Sequence-sharded
    parameters are initialized at their GLOBAL length and placed with
    :func:`shard_sp_params`.
    """
    from ..executor import _build_graph_fn, mirror_wrap
    graph_fn = _build_graph_fn(symbol, True)
    if symbol.list_auxiliary_states():
        raise NotImplementedError(
            'make_sp_train_step does not thread auxiliary state yet '
            '(BatchNorm moving stats); use stateless normalization in '
            'sequence-parallel symbols')
    seq_param_names = set(seq_param_names)
    data_names = set(data_names or ())

    def spmd(params, opt_state, batch, rng):
        def fwd(p):
            merged = dict(p)
            b = batch
            if compute_dtype is not None:
                merged = {k: (v.astype(compute_dtype)
                              if jnp.issubdtype(v.dtype, jnp.floating)
                              else v) for k, v in merged.items()}
                # batch entries named in data_names cast too (labels
                # never — the fit-step mixed-precision discipline)
                b = {k: (v.astype(compute_dtype)
                         if k in data_names and
                         jnp.issubdtype(v.dtype, jnp.floating) else v)
                     for k, v in batch.items()}
            merged.update(b)
            with sp_scope(seq_axis, attn_mode):
                outs, aux_upd = graph_fn(merged, {}, rng)
            return outs, aux_upd

        # mirror_wrap honors MXNET_BACKWARD_DO_MIRROR (activation
        # rematerialization — most valuable exactly at long context)
        (outs, _aux), vjp_fn = jax.vjp(mirror_wrap(fwd), params)
        cots = ([jnp.zeros_like(o) for o in outs], {})
        grads = vjp_fn(cots)[0]
        # replicated params: partial grads summed across seq shards;
        # seq-sharded params keep their shard-local gradient
        grads = {k: (g if k in seq_param_names
                     else jax.lax.psum(g, seq_axis))
                 for k, g in grads.items()}
        new_params, new_state = optimizer_update(params, grads,
                                                 opt_state)
        return outs, new_params, new_state

    # shardings: batch sharded on its seq dim, seq params on dim 0,
    # everything else replicated; momentum-style optimizer state
    # mirrors its parameter's spec
    def param_spec(name):
        return P(seq_axis) if name in seq_param_names else P()

    _mapped_cache = {}

    def step(params, opt_state, batch, rng):
        from jax import shard_map
        # the shard_map wrapper depends only on the pytree KEY sets —
        # build it once per structure, not per batch
        cache_key = (tuple(sorted(params)), tuple(sorted(batch)))
        mapped = _mapped_cache.get(cache_key)
        if mapped is None:
            p_specs = {k: param_spec(k) for k in params}

            def spec_like(state):
                if isinstance(state, dict):
                    return {k: (spec_like(v) if isinstance(v, dict)
                                else (param_spec(k) if k in p_specs
                                      else P()))
                            for k, v in state.items()}
                return P()

            st_specs = spec_like(opt_state)
            b_specs = dict(batch_specs or {})
            for k in batch:
                b_specs.setdefault(k, P(None, seq_axis))
            # graph outputs are per-shard (tokens-flattened) tensors;
            # dim-0 concatenation keeps them addressable —
            # shard-blocked row order, NOT the single-device
            # interleaving
            out_sp = [P(seq_axis)
                      for _ in range(len(symbol._outputs))]
            mapped = shard_map(
                spmd, mesh=mesh,
                in_specs=(p_specs, st_specs, b_specs, P()),
                out_specs=(out_sp, p_specs, st_specs),
                check_vma=False)
            _mapped_cache[cache_key] = mapped
        return mapped(params, opt_state, batch, rng)

    return step


def shard_sp_params(params, mesh, seq_axis='seq', seq_param_names=()):
    """Place params on the mesh: seq params sharded dim 0, the rest
    replicated — the layout :func:`make_sp_train_step` expects."""
    seq_param_names = set(seq_param_names)
    out = {}
    for k, v in params.items():
        spec = P(seq_axis) if k in seq_param_names else P()
        out[k] = jax.device_put(v, NamedSharding(mesh, spec))
    return out
