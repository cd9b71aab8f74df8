"""Fused training step — forward + backward + optimizer in ONE compiled
XLA program.

This is the TPU-native replacement for the reference's per-batch sequence
``forward() → backward() → kvstore push/pull → optimizer op per weight``
(``base_module.py:464-466`` → ``model.py:88-131``).  Fusing the whole step
lets XLA overlap gradient computation with the parameter update, eliminate
every intermediate HBM round-trip between stages, and (on a mesh) schedule
gradient all-reduces concurrently with remaining backward compute — the
optimization the reference approximates with its dependency-engine overlap
of kvstore pushes (SURVEY.md §3.1).

Buffer donation of params/optimizer state reproduces the in-place update
semantics (``kAddTo`` / fused ``sgd_mom_update``) without aliasing
machinery.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..executor import _build_graph_fn
from ..symbol import Symbol


def sgd_momentum_init(params):
    return {k: jnp.zeros_like(v) for k, v in params.items()}


def make_sgd_momentum(lr=0.05, momentum=0.9, wd=1e-4, rescale_grad=1.0):
    """Functional fused SGD+momentum (optimizer_op-inl.h semantics)."""
    def update(params, grads, state):
        new_params, new_state = {}, {}
        for k, w in params.items():
            g = grads[k].astype(w.dtype) * rescale_grad + wd * w
            m = momentum * state[k] - lr * g
            new_state[k] = m
            new_params[k] = w + m
        return new_params, new_state
    return update


def make_fit_step(symbol: Symbol, functional_opt, data_names=(),
                  compute_dtype=None, donate=True, _raw=False,
                  metric_fn=None, metric_label=None, metric_key=None,
                  health_action=None, shardings=None):
    """Build the fused step ``step(params, frozen, aux, opt_state, batch,
    lr_t, rng) -> (outputs, params, aux, opt_state)`` — forward, backward
    and every parameter update as ONE compiled program.

    With ``metric_fn`` (a pure ``(label, pred) -> deltas`` function, see
    ``EvalMetric.device_delta_fn``) the step additionally threads metric
    accumulators through the compiled program: the signature grows to
    ``step(params, frozen, aux, opt_state, metric_state, batch, lr_t,
    rng) -> (outputs, params, aux, opt_state, metric_state)`` where
    ``metric_state`` is a pytree of device scalars and the deltas
    computed from ``batch[metric_label]`` and the first output are added
    in-program — the eval metric never forces a per-batch host sync.

    With ``health_action`` (MXTPU_HEALTH_SENTINELS; one of 'warn'/
    'skip_update'/'abort') the step also folds the on-device health
    probe (``mxnet_tpu.health``): a global non-finite flag over the
    outputs and gradients, the global gradient norm and the
    update-to-weight ratio, accumulated into a ``health_state`` pytree
    of donated device scalars threaded right after the metric state
    (``..., metric_state, health_state, batch, ...``) and drained only
    at the metric drain points.  Under 'skip_update' a non-finite step's
    parameter/optimizer/aux/metric updates are masked in-program — the
    step becomes a no-op on training state, the reference behavior of
    skipping a bad batch without losing the step cadence.

    This replaces the reference's per-batch sequence forward → backward →
    per-parameter kvstore push/pull + updater loop
    (``base_module.py:464-466`` → ``model.py:88-131``).  ``lr_t`` is the
    host-computed scalar base lr (scheduler + Adam bias correction live
    on the host, per-parameter lr/wd multipliers are static inside
    ``functional_opt``), so lr changes never trigger recompilation.

    Under ``compute_dtype`` (bf16 mixed precision) params and the batch
    entries named in ``data_names`` are cast for the fwd/bwd compute;
    other batch entries (labels — class ids above 256 are not exactly
    representable in bf16) and master params / optimizer state stay f32
    — the same discipline as the reference's fp16 path
    (``test_dtype.py`` cifar fp16).

    With ``shardings`` (a :class:`mesh.FitShardings` — the dp×tp
    product path, docs/parallel.md) the SAME step function jits with
    explicit ``NamedSharding`` in/out shardings: batch split over the
    ``dp`` axis, params per the partition policy (replicated or
    tp-sharded), optimizer state ZeRO-sharded over ``dp``
    (``zero.zero_partition_spec``), metric/health scalars replicated.
    The math is untouched — XLA's SPMD partitioner emits the gradient
    all-reduce, ZeRO reduce-scatter/all-gather and any tp collectives
    inside the compiled program, so sharded and single-device programs
    compute the same model (PAPERS.md 1802.06949: MPI-style
    collectives belong in the compiled step, not a host-side loop).
    """
    # the step compiler: sequenced graph rewrites (fusion, folding,
    # layout planning) gated by MXTPU_FUSE — replaces the old
    # hardcoded fuse_bn_relu_conv1x1 call, so 'off' really is the
    # unfused program byte-for-byte (tools/check_fusion.py pins it)
    from ..fuse import apply_fuse_passes
    symbol = apply_fuse_passes(symbol, True)
    graph_fn = _build_graph_fn(symbol, True)
    # inputs an operator wants in their own dtype (OpDef.keep_dtype):
    # Embedding's token ids, which bf16 would round above 256, and the
    # float32 router of SparseExperts
    uncast = {n.inputs[i][0].name
              for n in symbol.topo_nodes() if not n.is_variable
              for i, name in enumerate(n.opdef().input_names(n.attrs))
              if name in n.opdef().keep_dtype and n.inputs[i][0].is_variable}
    data_names = tuple(n for n in data_names if n not in uncast)

    def step(params, frozen, aux, opt_state, batch, lr_t, rng,
             metric_state=None, health_state=None):
        raw_batch = batch
        if compute_dtype is not None:
            batch = {k: (v.astype(compute_dtype)
                         if k in data_names and
                         jnp.issubdtype(v.dtype, jnp.floating) else v)
                     for k, v in batch.items()}

        def fwd(p):
            merged = dict(frozen)
            merged.update(p)
            if compute_dtype is not None:
                merged = {k: (v.astype(compute_dtype)
                              if jnp.issubdtype(v.dtype, jnp.floating)
                              and k not in uncast else v)
                          for k, v in merged.items()}
            merged.update(batch)
            outs, aux_upd = graph_fn(merged, aux, rng)
            return outs, aux_upd

        from ..executor import mirror_wrap
        # the three parts of the step as scopes in every op's op_name
        # metadata (trace-time only; no HLO instruction changes)
        with jax.named_scope('forward_backward'):
            (outs, aux_upd), vjp_fn = jax.vjp(mirror_wrap(fwd), params)
            # zero cotangents: loss layers inject their gradient via
            # custom_vjp, the reference's SoftmaxOutput backward contract
            cots = ([jnp.zeros_like(o) for o in outs],
                    jax.tree_util.tree_map(jnp.zeros_like, aux_upd))
            grads = vjp_fn(cots)[0]
        new_aux = dict(aux)
        new_aux.update({k: v.astype(aux[k].dtype)
                        for k, v in aux_upd.items()})
        with jax.named_scope('optimizer'):
            new_params, new_opt = functional_opt.update(params, grads,
                                                        opt_state, lr_t)
        new_metric = None
        if metric_fn is not None:
            # metric deltas from the UNCAST label (class ids above 256
            # are not exactly representable in bf16) and the raw outputs
            with jax.named_scope('metric'):
                deltas = metric_fn(raw_batch[metric_label], outs[0])
                new_metric = jax.tree_util.tree_map(
                    lambda s, d: s + d, metric_state, deltas)
        new_health = None
        if health_action is not None:
            from .. import health as _health
            # sentinel probe over the RAW step results, before any
            # masking: outputs carry the loss-layer activations, grads
            # are where divergence surfaces first
            ok = _health.all_finite_tree((list(outs), grads))
            gnorm = _health.l2_norm_tree(grads)
            ratio = _health.update_ratio(params, new_params)
            if health_action == 'skip_update':
                # masked apply: a non-finite step leaves params /
                # optimizer state / aux / metric accumulators bit-for-
                # bit at their pre-step values (one fused select, no
                # extra host round-trip)
                def keep(new, old):
                    return jnp.where(ok, new, old)
                new_params = jax.tree_util.tree_map(keep, new_params,
                                                    params)
                new_opt = jax.tree_util.tree_map(keep, new_opt,
                                                 opt_state)
                new_aux = {k: keep(v, aux[k].astype(v.dtype))
                           for k, v in new_aux.items()}
                if new_metric is not None:
                    new_metric = jax.tree_util.tree_map(
                        keep, new_metric, metric_state)
            new_health = _health.fold_state(health_state, ok, gnorm,
                                            ratio)
        result = (outs, new_params, new_aux, new_opt)
        if new_metric is not None:
            result = result + (new_metric,)
        if new_health is not None:
            result = result + (new_health,)
        return result

    # re-order the threaded accumulator states ahead of the batch so
    # donate/batch argnums stay positional
    if metric_fn is not None and health_action is not None:
        fused = step

        def step_mh(params, frozen, aux, opt_state, metric_state,
                    health_state, batch, lr_t, rng):
            return fused(params, frozen, aux, opt_state, batch, lr_t,
                         rng, metric_state, health_state)
        step = step_mh
    elif metric_fn is not None:
        fused = step

        def step_m(params, frozen, aux, opt_state, metric_state, batch,
                   lr_t, rng):
            return fused(params, frozen, aux, opt_state, batch, lr_t,
                         rng, metric_state)
        step = step_m
    elif health_action is not None:
        fused = step

        def step_h(params, frozen, aux, opt_state, health_state, batch,
                   lr_t, rng):
            return fused(params, frozen, aux, opt_state, batch, lr_t,
                         rng, None, health_state)
        step = step_h

    if _raw:
        return step
    from .. import compile_cache
    # each trace records the batch avals + the metric fold key into the
    # warmup manifest (when MXTPU_COMPILE_CACHE is set): the exact
    # signature a warm-starting process must pre-lower.  metric_key is
    # recording-only metadata — the math is already baked into metric_fn.
    n_states = (metric_fn is not None) + (health_action is not None)
    step = compile_cache.traced(
        'fit_step', symbol, step,
        meta={'metric': compile_cache.jsonable(metric_key),
              'compute_dtype': (str(np.dtype(compute_dtype))
                                if compute_dtype is not None else None),
              'health': health_action,
              'mesh': shardings.plan.sig() if shardings is not None
              else None},
        batch_argnum=4 + n_states)
    jit_kw = {}
    if shardings is not None:
        plan = shardings.plan
        rep = plan.replicated
        # one replicated prefix per threaded accumulator state (metric,
        # health) — scalars, identical on every device
        state_sh = (rep,) * n_states
        # arg order after the reorder above: params, frozen, aux, opt,
        # [metric], [health], batch, lr_t, rng.  aux/batch use
        # pytree-prefix broadcast; params/frozen/opt are exact pytrees
        # built by the module (per-name partition + per-leaf ZeRO
        # specs — frozen params are PLACED per the partition policy
        # too, so a replicated prefix would mismatch the live arrays
        # on the AOT call path).
        frozen_sh = shardings.frozen if shardings.frozen is not None \
            else rep
        jit_kw['in_shardings'] = \
            (shardings.params, frozen_sh, rep, shardings.opt) \
            + state_sh + (plan.batch, rep, rep)
        # outputs carry the batch dim -> stay dp-sharded; params come
        # back per their partition spec (the partitioner's all-gather
        # closes the ZeRO loop), optimizer state STAYS dp-sharded
        jit_kw['out_shardings'] = \
            (plan.batch, shardings.params, rep, shardings.opt) + state_sh
    if donate:
        donate_argnums = (0, 2, 3) + tuple(range(4, 4 + n_states))
        return jax.jit(step, donate_argnums=donate_argnums, **jit_kw)
    return jax.jit(step, **jit_kw)


class _PlainUpdate(object):
    """Adapter presenting a bare ``update(params, grads, state)`` callable
    as a FunctionalOptimizer (the lr is baked into the callable)."""

    def __init__(self, fn):
        self._fn = fn

    def update(self, params, grads, state, lr_t):
        return self._fn(params, grads, state)


def make_train_step(symbol: Symbol, optimizer_update: Callable,
                    batch_names, donate=True,
                    compute_dtype=None):
    """Build ``step(params, aux, opt_state, batch, rng) ->
    (outputs, params, aux, opt_state)`` as one jitted program — the
    raw-API entry; a thin wrapper over :func:`make_fit_step` with
    no frozen params and the lr baked into ``optimizer_update``.

    ``batch_names`` is accepted for API stability (every non-batch arg
    is a parameter); the caller pre-casts batch data, so no batch
    casting happens here.
    """
    raw = make_fit_step(symbol, _PlainUpdate(optimizer_update),
                        data_names=(), compute_dtype=compute_dtype,
                        _raw=True)

    def step(params, aux, opt_state, batch, rng):
        return raw(params, {}, aux, opt_state, batch,
                   jnp.float32(0.0), rng)

    if donate:
        return jax.jit(step, donate_argnums=(0, 1, 2))
    return jax.jit(step)


def make_eval_step(symbol: Symbol, compute_dtype=None):
    """Jitted inference: ``(params, aux, batch, rng) -> outputs``."""
    # inference runs the same pass pipeline with is_train=False, where
    # the conv_bn_fold pass additionally folds EVERY post-norm
    # conv->bn chain straight into the conv weights
    from ..fuse import apply_fuse_passes
    symbol = apply_fuse_passes(symbol, False)
    graph_fn = _build_graph_fn(symbol, False)

    def step(params, aux, batch, rng):
        if compute_dtype is not None:
            params = {k: v.astype(compute_dtype)
                      for k, v in params.items()}
            batch = {k: (v.astype(compute_dtype)
                         if jnp.issubdtype(v.dtype, jnp.floating) else v)
                     for k, v in batch.items()}
        merged = dict(params)
        merged.update(batch)
        outs, _ = graph_fn(merged, aux, rng)
        return outs

    return jax.jit(step)
