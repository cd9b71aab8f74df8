"""Pipeline parallelism over a mesh axis — the GPipe-style microbatch
stream, TPU-native: every device holds ONE stage's weights and
activations hop stage-to-stage with ``lax.ppermute`` inside a
``shard_map``; the schedule is a ``lax.scan`` over
``num_microbatches + num_stages - 1`` ticks (fill + drain).

This is the 'pp' axis of the parallelism toolkit (``ring.py`` is sp,
``moe.py`` is ep, ``train_step``+mesh are dp/tp).  The reference
expressed pipeline splits through ``group2ctx`` device placement
(`executor.py` partitioned execution); on a TPU mesh the stream rides
ICI collectives inside one compiled program instead of host-ordered
per-device programs.

The collective-permute schedule is the standard public recipe (the
scaling-book / GSPMD pipelining pattern): at every tick each device
applies its stage to its current activation and permutes the result
forward; device 0 ingests the next microbatch, the last device banks
its finished microbatch.  SPMD means every device runs the same
program — the bank is only VALID on the last device, so the caller
reads that shard (``out_specs=P('pp')`` keeps it addressable).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def make_pipeline(mesh: Mesh, axis: str, stage_fn):
    """Build ``run(stage_weights, microbatches) -> outputs``.

    ``stage_fn(w, x) -> y`` is one stage's computation (same shape in
    and out, the pipeline contract).  ``stage_weights`` has a leading
    stage dimension sharded over ``axis`` (one stage per device);
    ``microbatches`` is ``(num_micro, mb, ...)``, fully replicated.
    Returns ``(num_micro, mb, ...)`` outputs (gathered from the last
    stage).
    """
    n_stages = mesh.shape[axis]
    axis_index = functools.partial(jax.lax.axis_index, axis)
    fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def spmd(w_local, xs):
        # w_local: this device's stage weights, leading dim 1 on every
        # leaf (works for a bare array or any pytree of stage params)
        # xs: (num_micro, mb, d) replicated input stream
        w = jax.tree_util.tree_map(lambda a: a[0], w_local)
        num_micro = xs.shape[0]
        idx = axis_index()
        # carries must be device-varying from the start (the shard_map
        # VMA type system rejects an unvarying->varying scan carry)
        def _vary(x):
            try:
                return jax.lax.pvary(x, axis)
            except (AttributeError, TypeError):
                return x
        zero = _vary(jnp.zeros_like(xs[0]))
        bank0 = _vary(jnp.zeros_like(xs))

        def tick(carry, t):
            cur, bank = carry
            # device 0 ingests microbatch t (while any remain); other
            # devices keep what the permute delivered last tick
            ingest = jnp.where(t < num_micro, t, 0)
            cur = jnp.where(idx == 0, xs[ingest], cur)
            y = stage_fn(w, cur)
            # bank finished microbatches on the LAST device: at tick t
            # it completes microbatch t - (n_stages - 1); branchless so
            # both paths have one varying type
            done = t - (n_stages - 1)
            slot = jnp.clip(done, 0, num_micro - 1)
            write = (done >= 0) & (idx == n_stages - 1)
            bank = bank.at[slot].set(jnp.where(write, y, bank[slot]))
            nxt = jax.lax.ppermute(y, axis, fwd)
            return (nxt, bank), None

        ticks = jnp.arange(num_micro + n_stages - 1)
        (_, bank), _ = jax.lax.scan(tick, (zero, bank0), ticks)
        # keep per-device banks addressable; only the last shard is
        # the real output
        return bank[None]

    from jax import shard_map
    mapped = shard_map(
        spmd, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(axis))

    def run(stage_weights, microbatches):
        banks = mapped(stage_weights, microbatches)
        return banks[-1]          # the last stage's bank

    return run


def reference_pipeline(stage_fn, stage_weights, microbatches):
    """Sequential oracle: every microbatch through every stage."""
    outs = []
    for x in microbatches:
        for w in stage_weights:
            x = stage_fn(w, x)
        outs.append(x)
    return jnp.stack(outs)


def make_pipeline_train_step(mesh: Mesh, axis: str, stage_fn, loss_fn,
                             opt_update, head_fn=None, remat=True):
    """GPipe forward+backward training step over the ``axis`` stages.

    The backward schedule is DERIVED, not hand-written: every primitive
    in the forward stream has a transpose (``ppermute`` reverses its
    permutation, ``scan`` unrolls in reverse, the masked ingest/bank
    selects route cotangents to the right microbatch), so
    ``jax.value_and_grad`` through :func:`make_pipeline` *is* the GPipe
    fill-drain backward — activations stream back through the same ICI
    links in reverse stage order.  This replaces the reference's
    host-ordered group2ctx backward (``graph_executor.cc`` partitioned
    RunOps + ``_CrossDeviceCopy`` grads; see
    ``example/model-parallel-lstm/lstm.py``) with one compiled SPMD
    program.

    Args:
      stage_fn: ``(w, x) -> y`` one stage's computation (shape-preserving).
      loss_fn:  ``(outs, labels) -> scalar`` applied to the last stage's
                ``(num_micro, mb, ...)`` output stream.
      opt_update: functional optimizer ``(params, grads, state) ->
                (new_params, new_state)`` over the {'stages': ...} tree —
                e.g. ``train_step.make_sgd_momentum(...)``.
      head_fn:  optional ``(outs) -> preds`` applied (replicated) after
                the pipeline, before ``loss_fn`` — the un-pipelined
                model head.
      remat:    rematerialize stage activations in the backward
                (``jax.checkpoint`` on the stage), bounding the stash to
                one activation per in-flight microbatch per device.

    Returns ``step(stage_weights, opt_state, microbatches, labels) ->
    (loss, new_weights, new_opt_state)``; jit-compatible; weights keep
    their leading stage dim sharded ``P(axis)``.
    """
    staged = jax.checkpoint(stage_fn) if remat else stage_fn
    run = make_pipeline(mesh, axis, staged)

    def loss(stage_weights, xs, ys):
        outs = run(stage_weights, xs)
        if head_fn is not None:
            outs = head_fn(outs)
        return loss_fn(outs, ys)

    def step(stage_weights, opt_state, xs, ys):
        lval, grads = jax.value_and_grad(loss)(stage_weights, xs, ys)
        new_w, new_state = apply_flat_opt(opt_update, stage_weights,
                                          grads, opt_state)
        return lval, new_w, new_state

    return step


def tree_as_flat_dict(tree):
    """Positional {'0': leaf, ...} view of a pytree — the adapter
    between arbitrary stage-weight pytrees and the framework's
    functional optimizers (which take flat name->array dicts).  The
    SINGLE naming authority: opt-state compatibility between
    :func:`pipeline_opt_init`, :func:`make_pipeline_train_step` and
    ``module.PipelineModule`` hangs on every caller using this."""
    leaves = jax.tree_util.tree_leaves(tree)
    return {str(i): leaf for i, leaf in enumerate(leaves)}


def apply_flat_opt(opt_update, params_tree, grads_tree, opt_state):
    """Run a flat-dict functional optimizer over pytree params."""
    leaves, treedef = jax.tree_util.tree_flatten(params_tree)
    new_flat, new_state = opt_update(tree_as_flat_dict(params_tree),
                                     tree_as_flat_dict(grads_tree),
                                     opt_state)
    new_tree = jax.tree_util.tree_unflatten(
        treedef, [new_flat[str(i)] for i in range(len(leaves))])
    return new_tree, new_state


def pipeline_opt_init(stage_weights, state_init):
    """Optimizer state for :func:`make_pipeline_train_step`:
    ``state_init`` (e.g. ``train_step.sgd_momentum_init``) applied to the
    flattened stage-weight tree, matching the step's internal naming."""
    return state_init(tree_as_flat_dict(stage_weights))


# ---------------------------------------------------------------------------
# Explicit 1F1B schedule
# ---------------------------------------------------------------------------

def make_pipeline_1f1b(mesh: Mesh, axis: str, stage_fn, loss_grad_fn):
    """One-forward-one-backward pipeline training with a BOUNDED
    activation stash: each device holds at most ``n_stages`` stage
    inputs regardless of the microbatch count, vs the GPipe/AD path
    (:func:`make_pipeline_train_step`) whose stash grows with
    ``num_micro``.  Use it when microbatches >> stages (long-context
    accumulation); its SPMD form computes both the fwd and bwd branch
    every tick (masked), so for small ``num_micro`` the AD path is
    faster.

    Schedule (non-interleaved 1F1B; device d, microbatch i, n stages):
      fwd  at tick  i + d          while i < n - d   (warmup)
                    2i + d         afterwards        (steady state)
      bwd  at tick  2n - 1 - d + 2i
    over ``2 * (num_micro + n - 1)`` ticks.  Forward activations hop
    right with a gap of up to n ticks (an n-slot ring buffer indexed
    by microbatch mod n absorbs it); backward cotangents hop left with
    a gap of exactly one tick.

    Args:
      stage_fn: ``(w, x) -> y`` shape-preserving stage.
      loss_grad_fn: ``(y, target) -> (loss_scalar, dy)`` applied on the
        LAST stage's outputs per microbatch.

    Returns ``run(stage_weights, xs, ys) -> (mean_loss, grads)`` with
    ``grads`` matching the stage-weights pytree (leading stage dim —
    each device's shard holds d/d(its stage weights)).
    """
    n = mesh.shape[axis]
    fwd_perm = [(i, (i + 1) % n) for i in range(n)]
    bwd_perm = [(i, (i - 1) % n) for i in range(n)]

    def _fwd_index(t, d, num_micro):
        """Microbatch this device forwards at tick t, or -1."""
        warm = t - d                       # i if in warmup window
        steady = (t - d) // 2              # i if in steady window
        warm_ok = (warm >= 0) & (warm < jnp.minimum(n - d, num_micro))
        steady_ok = ((t - d) % 2 == 0) & (steady >= n - d) \
            & (steady < num_micro)
        return jnp.where(warm_ok, warm,
                         jnp.where(steady_ok, steady, -1))

    def _bwd_index(t, d, num_micro):
        num = t - (2 * n - 1 - d)
        i = num // 2
        ok = (num >= 0) & (num % 2 == 0) & (i < num_micro)
        return jnp.where(ok, i, -1)

    def spmd(w_local, xs, ys):
        w = jax.tree_util.tree_map(lambda a: a[0], w_local)
        d = jax.lax.axis_index(axis)
        num_micro = xs.shape[0]

        def _vary(x):
            try:
                return jax.lax.pvary(x, axis)
            except (AttributeError, TypeError):
                return x

        mb_shape = xs.shape[1:]
        in_buf0 = _vary(jnp.zeros((n,) + mb_shape, xs.dtype))
        stash0 = _vary(jnp.zeros((n,) + mb_shape, xs.dtype))
        cot0 = _vary(jnp.zeros(mb_shape, xs.dtype))
        # w is already device-varying (the sharded input): its
        # zeros inherit the vma; only replicated-born carries need
        # the explicit pvary
        g0 = jax.tree_util.tree_map(jnp.zeros_like, w)
        loss0 = _vary(jnp.zeros((), jnp.float32))

        def tick(carry, t):
            in_buf, cot_in, stash, gacc, lacc = carry
            fi = _fwd_index(t, d, num_micro)
            bi = _bwd_index(t, d, num_micro)
            fwd_on = fi >= 0
            bwd_on = bi >= 0
            fslot = jnp.clip(fi, 0) % n
            bslot = jnp.clip(bi, 0) % n

            # ---- forward branch (masked) ----
            x_in = jnp.where(d == 0, xs[jnp.clip(fi, 0)],
                             in_buf[fslot])
            y = stage_fn(w, x_in)
            stash = jnp.where(fwd_on,
                              stash.at[fslot].set(x_in), stash)

            # ---- backward branch (masked; rematerializes the stage) -
            x_b = stash[bslot]
            y_b, vjp_fn = jax.vjp(stage_fn, w, x_b)
            loss_i, dy = loss_grad_fn(y_b, ys[jnp.clip(bi, 0)])
            cot = jnp.where(d == n - 1, dy.astype(y_b.dtype), cot_in)
            dw, dx = vjp_fn(cot)
            gacc = jax.tree_util.tree_map(
                lambda acc, g: acc + jnp.where(bwd_on, g, 0.0),
                gacc, dw)
            lacc = lacc + jnp.where(bwd_on & (d == n - 1),
                                    loss_i.astype(jnp.float32), 0.0)

            # ---- communication ----
            y_sent = jax.lax.ppermute(
                jnp.where(fwd_on, y, 0.0), axis, fwd_perm)
            # receiver slots the incoming activation by the SENDER's
            # microbatch id (= the id the receiver will consume)
            sender_fi = _fwd_index(t, d - 1, num_micro)
            recv_on = (sender_fi >= 0) & (d > 0)
            rslot = jnp.clip(sender_fi, 0) % n
            in_buf = jnp.where(recv_on,
                               in_buf.at[rslot].set(y_sent), in_buf)
            dx_sent = jax.lax.ppermute(
                jnp.where(bwd_on, dx, 0.0), axis, bwd_perm)
            return (in_buf, dx_sent, stash, gacc, lacc), None

        ticks = jnp.arange(2 * (num_micro + n - 1))
        (_, _, _, grads, loss_sum), _ = jax.lax.scan(
            tick, (in_buf0, cot0, stash0, g0, loss0), ticks)
        # every device reports the same mean loss (psum the last
        # device's accumulation), and grads are d(mean_loss)/dw —
        # the SAME scale contract as make_pipeline_train_step's
        # value_and_grad, so the two paths are drop-in interchangeable
        mean_loss = jax.lax.psum(loss_sum, axis) / num_micro
        grads_out = jax.tree_util.tree_map(
            lambda g: g[None] / num_micro, grads)
        return mean_loss, grads_out

    from jax import shard_map
    return shard_map(spmd, mesh=mesh,
                     in_specs=(P(axis), P(), P()),
                     out_specs=(P(), P(axis)))
