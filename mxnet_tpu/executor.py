"""Executor — compiled evaluation of a bound Symbol.

TPU-native replacement for the reference graph executor
(``src/executor/graph_executor.cc:716 Executor::Bind``, ``Forward`` at
``:26``, ``Backward`` at ``:39``, ``RunOps`` at ``:669``).

Mapping of reference machinery onto XLA:

- ``nnvm::pass::Gradient`` + ``AggregateGradient``
  (``graph_executor.cc:81-222``) → ``jax.vjp`` over the traced forward
  function.  XLA differentiates the *whole* program, so gradient
  aggregation, inplace-addto detection (``inplace_addto_detect_pass.cc``)
  and mirroring are compiler concerns, not framework passes.
- ``PlanMemory`` + ``InitDataEntryMemory`` pool reuse
  (``graph_executor.cc:416,423-534``) → XLA buffer assignment; argument
  donation stands in for ``shared_exec`` memory sharing.
- ``InitCachedOps`` engine-op caching (``:537-667``) → the jit cache.
- ``group2ctx`` + ``PlaceDevice`` + ``_CrossDeviceCopy`` (``:225-314``) →
  per-partition jit with explicit ``jax.device_put`` transfers between
  context groups (model parallelism); see ``_forward_partitioned``.
- The monitor callback (``MXExecutorSetMonitorCallback``,
  ``c_api_executor.cc:157``) runs the graph node-by-node un-jitted, the
  analogue of dropping to NaiveEngine for debugging.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import compile_cache, instrument
from .base import MXNetError
from .context import Context, current_context
from .ndarray import NDArray, zeros as nd_zeros, RANDOM
from .ops.registry import KEEP, marks
from .symbol import Symbol

__all__ = ['Executor', 'simple_bind']

# what a mirror stage keeps beside its inputs: the values its ops mark
_KEEP_POLICY = jax.checkpoint_policies.save_only_these_names(KEEP)


def _build_graph_fn(symbol: Symbol, is_train: bool, monitor_re=None,
                    _count=True):
    """Build the pure function (args, aux, rng) -> (outputs, aux_updates).

    ``is_train`` is baked in (static), so train and eval compile to
    separate XLA programs — mirroring how the reference executor skips
    backward nodes for inference (``RunOps(false, 0, num_forward_nodes)``).

    With ``monitor_re`` (a compiled regex), the function returns a third
    value: a dict of matching intermediate outputs by name.  This is how
    the monitor taps tensors WITHOUT dropping to the interpreter — the
    taps become extra jit outputs, the analogue of the reference tapping
    per-node outputs at full engine speed
    (``graph_executor.cc:695-710``).
    """
    # every counted call is a fresh program build that XLA must trace
    # and compile — the executor-level retrace signal (InitCachedOps
    # analogue); shape-only uses (eval_shape in _out_avals) pass
    # _count=False so the counter tracks real compilations
    if _count:
        instrument.inc('executor.graph_builds')
    nodes = symbol.topo_nodes()
    out_entries = symbol._outputs
    units = _mirror_stage_units(nodes, out_entries) \
        if is_train and monitor_re is None else \
        [([(i, n)], None, None) for i, n in enumerate(nodes)
         if not n.is_variable]
    # the values the mirror stages keep by name, counted at the first trace
    counted = [not _count]

    def run(members, entry_vals, aux_updates, monitored, rng):
        """Apply the op nodes ``members`` ((index, node) pairs) in order,
        reading and writing ``entry_vals``."""
        for i, node in members:
            op = node.opdef()
            ins = [entry_vals[(id(n), x)] for n, x in node.inputs]
            node_rng = jax.random.fold_in(rng, i) if op.takes_rng else rng
            # operator and node name into every op's op_name metadata,
            # forward and (as transpose(jvp(...))) backward; costs only
            # while JAX traces and changes no HLO instruction
            with jax.named_scope('%s/%s' % (node.op, node.name)):
                outs, aux_upd = op.apply(node.attrs, ins, is_train,
                                         node_rng)
            for j, o in enumerate(outs):
                entry_vals[(id(node), j)] = o
            if monitor_re is not None:
                for j, oname in enumerate(node.output_names()):
                    if monitor_re.match(oname):
                        monitored[oname] = outs[j]
            if aux_upd:
                # map op-local aux names -> graph variable names
                n_main = len(op.input_names(node.attrs))
                aux_nms = op.aux_names(node.attrs)
                for local_name, val in aux_upd.items():
                    slot = aux_nms.index(local_name)
                    var_node = node.inputs[n_main + slot][0]
                    aux_updates[var_node.name] = val

    def fn(arg_values: Dict[str, jnp.ndarray],
           aux_values: Dict[str, jnp.ndarray], rng):
        entry_vals: Dict[Tuple[int, int], jnp.ndarray] = {}
        aux_updates: Dict[str, jnp.ndarray] = {}
        monitored: Dict[str, jnp.ndarray] = {}
        for node in nodes:
            if not node.is_variable:
                continue
            if node.name in arg_values:
                entry_vals[(id(node), 0)] = arg_values[node.name]
            elif node.name in aux_values:
                entry_vals[(id(node), 0)] = aux_values[node.name]
            else:
                raise MXNetError('unbound variable %s' % node.name)
        kept = [0]
        for members, taken, given in units:
            if taken is None:
                run(members, entry_vals, aux_updates, monitored, rng)
                continue

            # one mirror stage: kept are its inputs and what its ops mark
            # (ops.registry.keep), the rest of its inside is computed again
            # in the backward pass; a stage that marks nothing keeps what a
            # plain jax.checkpoint keeps
            def stage(values, rng, members=members, taken=taken,
                      given=given):
                local, aux_local = dict(zip(taken, values)), {}
                before = marks()
                run(members, local, aux_local, None, rng)
                kept[0] += marks() - before
                return [local[e] for e in given], aux_local

            outs, aux_local = jax.checkpoint(stage, policy=_KEEP_POLICY)(
                [entry_vals[e] for e in taken], rng)
            entry_vals.update(zip(given, outs))
            aux_updates.update(aux_local)
        if not counted[0]:
            counted[0] = True
            if kept[0]:
                instrument.inc('executor.mirror_kept', kept[0])
        outputs = [entry_vals[(id(n), x)] for n, x in out_entries]
        if monitor_re is not None:
            return outputs, aux_updates, monitored
        return outputs, aux_updates

    return fn


def _mirror_stage(node):
    return node._extra_attr.get('mirror_stage') or \
        node._extra_attr.get('__mirror_stage__')


def _mirror_stage_units(nodes, out_entries):
    """The op nodes of a graph in order, as ``(members, taken, given)``:
    a run of consecutive op nodes that carry the same ``mirror_stage``
    attribute (``mx.AttrScope(mirror_stage=...)``, the reference's
    attribute for its memory-mirror pass, ``graph_executor.cc``) is one
    unit with the entries it takes from outside and those it gives to
    later nodes or to the graph's outputs; every other op node is a unit
    of its own with ``taken`` None."""
    ops = [(i, n) for i, n in enumerate(nodes) if not n.is_variable]
    units, run = [], []
    for item in ops:
        stage = _mirror_stage(item[1])
        if run and stage != _mirror_stage(run[-1][1]):
            units.append(run)
            run = []
        if stage is None:
            units.append([item])
        else:
            run.append(item)
    if run:
        units.append(run)
    used_later = {}     # entry -> index of the last op node that reads it
    for i, node in ops:
        for n, x in node.inputs:
            used_later[(id(n), x)] = i
    for n, x in out_entries:
        used_later[(id(n), x)] = len(nodes)
    out = []
    for members in units:
        if _mirror_stage(members[0][1]) is None:
            out.append((members, None, None))
            continue
        inside = {id(n) for _, n in members}
        taken, given = [], []
        for _, node in members:
            for n, x in node.inputs:
                if id(n) not in inside and (id(n), x) not in taken:
                    taken.append((id(n), x))
        last = members[-1][0]
        for _, node in members:
            for j in range(node.num_outputs()):
                if used_later.get((id(node), j), -1) > last:
                    given.append((id(node), j))
        out.append((members, taken, given))
    return out


def mirror_wrap(f):
    """Apply the MXNET_BACKWARD_DO_MIRROR memory/compute trade to a
    differentiated forward function (reference mirror pass,
    ``graph_executor.cc:199-216``): wrap it in ``jax.checkpoint`` so XLA
    rematerializes activations in backward instead of storing them.
    Policy 'dots' keeps matmul/conv results (recompute only cheap
    elementwise nodes — closest to the reference, which mirrors
    activation/BN-type nodes); 'nothing' saves nothing."""
    from . import config
    if not config.get('MXNET_BACKWARD_DO_MIRROR'):
        return f
    policy_name = config.get('MXNET_BACKWARD_MIRROR_POLICY')
    if policy_name == 'dots':
        # jax's checkpoint_dots covers dot_general only; conv nets need
        # conv outputs saved too or 'dots' degenerates to full remat
        # for the expensive ops (the opposite of the reference mirror,
        # which recomputes only cheap activation/BN nodes)
        def policy(prim, *_, **__):
            return prim.name in ('dot_general', 'conv_general_dilated')
    elif policy_name == 'nothing':
        policy = jax.checkpoint_policies.nothing_saveable
    else:
        raise MXNetError('MXNET_BACKWARD_MIRROR_POLICY must be '
                         "'dots' or 'nothing', got %r" % policy_name)
    return jax.checkpoint(f, policy=policy)


class Executor:
    """A bound computation (reference ``python/mxnet/executor.py``)."""

    def __init__(self, symbol: Symbol, ctx: Context,
                 args, args_grad=None, grad_req='write', aux_states=None,
                 group2ctx=None, shared_exec=None):
        self._symbol = symbol
        self._ctx = ctx if isinstance(ctx, Context) else Context(ctx)
        self._group2ctx = group2ctx or {}
        self._monitor_callback = None
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.output_names = symbol.list_outputs()

        self.arg_dict = self._normalize(args, self.arg_names, 'args')
        self.aux_dict = self._normalize(aux_states, self.aux_names,
                                        'aux_states', allow_none=True)
        self.grad_dict = self._normalize(args_grad, self.arg_names,
                                         'args_grad', allow_none=True,
                                         partial_ok=True)
        if isinstance(grad_req, str):
            self.grad_req = {n: grad_req for n in self.arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self.grad_req = dict(zip(self.arg_names, grad_req))
        else:
            self.grad_req = {n: grad_req.get(n, 'null')
                             for n in self.arg_names}
        for n in self.arg_names:
            if n not in self.grad_dict:
                self.grad_req[n] = 'null'
        self._grad_names = [n for n in self.arg_names
                            if self.grad_req.get(n, 'null') != 'null'
                            and n in self.grad_dict]

        self._jit_fwd: Dict[bool, object] = {}
        self._jit_fwd_mon: Dict[tuple, object] = {}
        self._jit_fwd_bwd = None
        self._fuse_cache: Dict[bool, Symbol] = {}
        self._monitor_pattern = None
        self._pending_grads = None
        self._bwd_seen = False
        self._rng_seed = 0
        self.outputs: List[NDArray] = []
        self._last_is_train = False

    @staticmethod
    def _normalize(values, names, what, allow_none=False, partial_ok=False):
        if values is None:
            if allow_none:
                return {}
            raise MXNetError('%s must be provided' % what)
        if isinstance(values, dict):
            out = dict(values)
        else:
            values = list(values)
            if len(values) != len(names) and not partial_ok:
                raise MXNetError('length of %s (%d) does not match '
                                 'number of names (%d)'
                                 % (what, len(values), len(names)))
            out = {n: v for n, v in zip(names, values) if v is not None}
        for k, v in out.items():
            if not isinstance(v, NDArray):
                raise TypeError('%s[%s] must be NDArray' % (what, k))
        return out

    def _program_symbol(self, is_train):
        """The symbol actually compiled on the ONE-PROGRAM jit paths:
        the step-compiler pass pipeline (``fuse.apply_fuse_passes``,
        ``MXTPU_FUSE`` knob) runs here, once per (executor, mode).
        Monitored / partitioned / eager paths keep the original symbol
        — taps and ctx_group placement key on original node names.
        With the knob off this is the bound symbol object itself
        (byte-identical program)."""
        key = bool(is_train)
        cached = self._fuse_cache.get(key)
        if cached is None:
            from .fuse import apply_fuse_passes
            cached = apply_fuse_passes(self._symbol, key)
            self._fuse_cache[key] = cached
        return cached

    # -- forward -----------------------------------------------------------
    def forward(self, is_train=False, **kwargs):
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError('unknown argument %s' % k)
            src = v if isinstance(v, NDArray) else NDArray(jnp.asarray(v))
            self.arg_dict[k]._set_data(src.handle)
        self._last_is_train = is_train
        self._pending_grads = None
        if self._group2ctx:
            if self._monitor_callback is not None:
                return self._forward_eager(is_train)
            return self._forward_partitioned(is_train)
        if self._monitor_callback is not None:
            return self._forward_monitored(is_train)
        if is_train and self._grad_names and self._bwd_seen:
            # this executor's usage pattern is forward(); backward():
            # loss layers inject their own cotangents, so run the ONE
            # fused fwd+bwd program now and let backward() just write
            # the cached grads instead of re-running the forward inside
            # the backward program (the reference kept per-node outputs
            # alive in the memory pool for the same reason,
            # graph_executor.cc InitDataEntryMemory).  Gated on a
            # backward() having happened once (_bwd_seen) so training-
            # mode forwards that never backward — MC-dropout loops,
            # BN-stat passes — keep the cheap forward-only program.
            return self._forward_with_grads()
        fn = self._jit_fwd.get(is_train)
        fresh = fn is None
        if fresh:
            instrument.inc('executor.retraces')
            prog_symbol = self._program_symbol(is_train)
            graph_fn = _build_graph_fn(prog_symbol, is_train)
            # per-step key derived inside the program (an eager fold_in
            # costs ~1ms host dispatch per call)
            fn = jax.jit(compile_cache.traced(
                'forward', prog_symbol,
                lambda args, aux, key, seed: graph_fn(
                    args, aux, jax.random.fold_in(key, seed)),
                meta={'is_train': bool(is_train)}))
            self._jit_fwd[is_train] = fn
        else:
            instrument.inc('executor.cache_hits')
        self._rng_seed += 1
        args = {k: v.handle for k, v in self.arg_dict.items()}
        aux = {k: v.handle for k, v in self.aux_dict.items()}
        if fresh:
            from . import perfwatch
            if perfwatch.capture_on():
                # AOT-capture the program the first call would jit
                # anyway: the compiled executable exposes cost/memory
                # analysis (the performance plane's per-executable
                # accounting — every Predictor bucket executor lands
                # here with its own shapes), and later calls go
                # straight to it
                fn = self._perf_aot_capture(fn, is_train, args, aux)
        with instrument.span('executor.forward', cat='executor'):
            try:
                outs, aux_updates = fn(args, aux, RANDOM.key,
                                       np.uint32(self._rng_seed))
            except Exception as exc:
                from . import perfwatch
                perfwatch.on_error(exc, 'forward',
                                   self._perf_sig(is_train, args))
                raise
        for name, val in aux_updates.items():
            self.aux_dict[name]._set_data(val)
        self.outputs = [NDArray(o, self._ctx) for o in outs]
        return self.outputs


    def _perf_sig(self, is_train, args):
        """Program signature of this executor's forward: symbol
        fingerprint + mode + bound avals (distinct per Predictor
        bucket).  Only built when the performance plane consumes it."""
        return (compile_cache.fingerprint(self._symbol),
                'train' if is_train else 'infer',
                tuple(sorted((k, tuple(int(d) for d in v.shape),
                              str(v.dtype)) for k, v in args.items())))

    def _perf_aot_capture(self, jitfn, is_train, args, aux):
        """Compile the freshly-built forward through the AOT API and
        register its cost/memory analysis (perfwatch leg 1).  Returns a
        callable that runs the compiled executable, degrading to the
        jit path permanently on aval/sharding drift; on any capture
        failure the jit fn comes back untouched."""
        from . import perfwatch
        sig = self._perf_sig(is_train, args)
        try:
            compiled = jitfn.lower(args, aux, RANDOM.key,
                                   np.uint32(self._rng_seed)).compile()
        except Exception:
            return jitfn
        perfwatch.register_executable('forward', sig, compiled)
        state = [compiled]

        def call(*a):
            c = state[0]
            if c is not None:
                try:
                    return c(*a)
                except Exception as exc:
                    if perfwatch.is_oom(exc):
                        raise
                    state[0] = None     # drift: jit path from now on
            return jitfn(*a)

        self._jit_fwd[is_train] = call
        return call

    def _gathered_handles(self):
        """Handles for the one-program jit paths.  Under group2ctx the
        arrays live on their group devices; gather them to the primary
        device first (the explicit-transfer analogue of
        _CrossDeviceCopy) so jit sees consistent placement.  The
        per-group compiled path is _forward_partitioned."""
        grad_args = {k: self.arg_dict[k].handle for k in self._grad_names}
        other_args = {k: v.handle for k, v in self.arg_dict.items()
                      if k not in grad_args}
        aux = {k: v.handle for k, v in self.aux_dict.items()}
        if self._group2ctx:
            dev = self._ctx.jax_device
            put = lambda d: {k: jax.device_put(v, dev)
                             for k, v in d.items()}
            return put(grad_args), put(other_args), put(aux)
        return grad_args, other_args, aux

    def _forward_with_grads(self):
        """Training forward that also computes gradients (zero head
        cotangents — the loss-layer convention); ``backward(None)``
        then costs nothing extra."""
        self._dispatch_fwd_bwd()
        self._rng_seed += 1
        grad_args, other_args, aux = self._gathered_handles()
        with instrument.span('executor.forward_backward', cat='executor'):
            try:
                outs, aux_upd, grads = self._jit_fwd_bwd(
                    grad_args, other_args, aux, RANDOM.key,
                    np.uint32(self._rng_seed), None)
            except Exception as exc:
                from . import perfwatch
                perfwatch.on_error(exc, 'forward_backward',
                                   self._perf_sig(True, grad_args))
                raise
        for name, val in aux_upd.items():
            self.aux_dict[name]._set_data(val)
        self.outputs = [NDArray(o, self._ctx) for o in outs]
        self._pending_grads = grads
        return self.outputs

    def _next_rng(self):
        # one key per step; ops fold in their node index
        self._rng_seed += 1
        return jax.random.fold_in(RANDOM.key, self._rng_seed)

    def _forward_monitored(self, is_train):
        """Monitored forward at full compiled speed: intermediates
        matching the monitor's pattern are staged as extra jit outputs
        and handed to the callback after the step — no interpreter
        fallback (reference taps ran inside the engine,
        ``graph_executor.cc:695-710``)."""
        import re as _re
        pattern = self._monitor_pattern or _re.compile('.*')
        key = (is_train, pattern.pattern)
        fn = self._jit_fwd_mon.get(key)
        if fn is None:
            instrument.inc('executor.retraces')
            graph_fn = _build_graph_fn(self._symbol, is_train,
                                       monitor_re=pattern)
            fn = jax.jit(compile_cache.traced(
                'forward_monitored', self._symbol,
                lambda args, aux, k, seed: graph_fn(
                    args, aux, jax.random.fold_in(k, seed)),
                meta={'is_train': bool(is_train)}))
            self._jit_fwd_mon[key] = fn
        else:
            instrument.inc('executor.cache_hits')
        self._rng_seed += 1
        args = {k: v.handle for k, v in self.arg_dict.items()}
        aux = {k: v.handle for k, v in self.aux_dict.items()}
        outs, aux_updates, monitored = fn(args, aux, RANDOM.key,
                                          np.uint32(self._rng_seed))
        for name, val in aux_updates.items():
            self.aux_dict[name]._set_data(val)
        self.outputs = [NDArray(o, self._ctx) for o in outs]
        for name, val in monitored.items():
            self._monitor_callback(name, NDArray(val, self._ctx))
        return self.outputs

    def _node_ctx(self, node):
        grp = node._extra_attr.get('ctx_group') or \
            node._extra_attr.get('__ctx_group__')
        if grp and grp in self._group2ctx:
            return self._group2ctx[grp]
        return self._ctx

    # -- partitioned (group2ctx) forward -----------------------------------
    def _build_partition_plan(self, is_train):
        """Split the topo order into contiguous per-context segments and
        jit each segment — the compiled analogue of the reference's
        ``PlaceDevice`` pass + ``_CrossDeviceCopy`` insertion
        (``graph_executor.cc:253-313``).  Cross-segment tensors move with
        explicit ``device_put``; within a segment XLA fuses freely."""
        nodes = self._symbol.topo_nodes()
        comp = [n for n in nodes if not n.is_variable]
        node_idx = {id(n): i for i, n in enumerate(nodes)}

        segments = []           # (ctx, [nodes])
        for n in comp:
            ctx = self._node_ctx(n)
            if segments and segments[-1][0] == ctx:
                segments[-1][1].append(n)
            else:
                segments.append((ctx, [n]))

        def ekey(node, j):
            return '%d:%d' % (node_idx[id(node)], j)

        producer_seg = {}       # entry key -> segment index (-1 for vars)
        for n in nodes:
            if n.is_variable:
                producer_seg[ekey(n, 0)] = -1
        for si, (_, seg_nodes) in enumerate(segments):
            for n in seg_nodes:
                for j in range(len(n.output_names())):
                    producer_seg[ekey(n, j)] = si

        out_keys = [ekey(n, j) for n, j in self._symbol._outputs]
        seg_inputs = [set() for _ in segments]
        seg_outputs = [set() for _ in segments]
        var_nodes = {}
        for si, (_, seg_nodes) in enumerate(segments):
            for n in seg_nodes:
                for src, j in n.inputs:
                    k = ekey(src, j)
                    ps = producer_seg[k]
                    if ps == -1:
                        seg_inputs[si].add(k)
                        var_nodes[k] = src
                    elif ps != si:
                        seg_inputs[si].add(k)
                        seg_outputs[ps].add(k)
        node_by_idx = {node_idx[id(n)]: n for n in nodes}
        for k in out_keys:
            ps = producer_seg[k]
            if ps >= 0:
                seg_outputs[ps].add(k)
            else:
                # graph output that is a bare variable: read it straight
                # from the bound arrays at call time
                var_nodes[k] = node_by_idx[int(k.split(':')[0])]

        plan = []
        for si, (ctx, seg_nodes) in enumerate(segments):
            in_keys = sorted(seg_inputs[si])
            outk = sorted(seg_outputs[si])
            seg_nodes_ = list(seg_nodes)

            def make_fn(seg_nodes=seg_nodes_, in_keys=tuple(in_keys),
                        out_keys_seg=tuple(outk)):
                def fn(env, rng):
                    entry = dict(env)
                    aux_updates = {}
                    for n in seg_nodes:
                        op = n.opdef()
                        ins = [entry[ekey(src, j)] for src, j in n.inputs]
                        node_rng = jax.random.fold_in(
                            rng, node_idx[id(n)]) if op.takes_rng else rng
                        outs, aux_upd = op.apply(n.attrs, ins, is_train,
                                                 node_rng)
                        for j, o in enumerate(outs):
                            entry[ekey(n, j)] = o
                        if aux_upd:
                            n_main = len(op.input_names(n.attrs))
                            aux_nms = op.aux_names(n.attrs)
                            for local, val in aux_upd.items():
                                var_node = n.inputs[
                                    n_main + aux_nms.index(local)][0]
                                aux_updates[var_node.name] = val
                    return {k: entry[k] for k in out_keys_seg}, aux_updates
                return fn

            plan.append({'ctx': ctx,
                         'fn': jax.jit(compile_cache.traced(
                             'forward_partitioned', self._symbol,
                             make_fn(), meta={'segment': si})),
                         'in_keys': in_keys, 'out_keys': outk,
                         # span label built once here, not per step
                         'span': 'executor.segment[%d]@%s' % (si, ctx)})
        return {'segments': plan, 'var_nodes': var_nodes,
                'out_keys': out_keys}

    def _forward_partitioned(self, is_train):
        if not hasattr(self, '_partition_plans'):
            self._partition_plans = {}
        plan = self._partition_plans.get(is_train)
        if plan is None:
            instrument.inc('executor.retraces')
            plan = self._build_partition_plan(is_train)
            self._partition_plans[is_train] = plan
        else:
            instrument.inc('executor.cache_hits')
        rng = self._next_rng()
        env = {}
        for k, var in plan['var_nodes'].items():
            name = var.name
            if name in self.arg_dict:
                env[k] = self.arg_dict[name].handle
            elif name in self.aux_dict:
                env[k] = self.aux_dict[name].handle
            else:
                raise MXNetError('unbound variable %s' % name)
        for seg in plan['segments']:
            with instrument.span(seg['span'], cat='executor'):
                dev = seg['ctx'].jax_device
                seg_env = {k: jax.device_put(env[k], dev)
                           for k in seg['in_keys']}
                outs, aux_updates = seg['fn'](seg_env, rng)
            env.update(outs)
            for name, val in aux_updates.items():
                self.aux_dict[name]._set_data(val)
        self.outputs = [NDArray(env[k], self._ctx)
                        for k in plan['out_keys']]
        return self.outputs

    def _forward_eager(self, is_train):
        """Node-by-node execution: monitor taps + group2ctx placement.

        The model-parallel path: each node runs on its context group's
        device; inputs living elsewhere are device_put across — the
        analogue of ``_CrossDeviceCopy`` insertion
        (``graph_executor.cc:301``).
        """
        nodes = self._symbol.topo_nodes()
        entry_vals = {}
        rng = self._next_rng()
        for i, node in enumerate(nodes):
            if node.is_variable:
                if node.name in self.arg_dict:
                    val = self.arg_dict[node.name].handle
                elif node.name in self.aux_dict:
                    val = self.aux_dict[node.name].handle
                else:
                    raise MXNetError('unbound variable %s' % node.name)
                entry_vals[(id(node), 0)] = val
                continue
            op = node.opdef()
            dev = self._node_ctx(node).jax_device
            ins = []
            for n, x in node.inputs:
                v = entry_vals[(id(n), x)]
                if self._group2ctx:
                    v = jax.device_put(v, dev)
                ins.append(v)
            node_rng = jax.random.fold_in(rng, i) if op.takes_rng else rng
            outs, aux_upd = op.apply(node.attrs, ins, is_train, node_rng)
            for j, o in enumerate(outs):
                entry_vals[(id(node), j)] = o
            if aux_upd:
                n_main = len(op.input_names(node.attrs))
                aux_nms = op.aux_names(node.attrs)
                for local_name, val in aux_upd.items():
                    var_node = node.inputs[n_main + aux_nms.index(local_name)][0]
                    self.aux_dict[var_node.name]._set_data(val)
            if self._monitor_callback is not None:
                for j, oname in enumerate(node.output_names()):
                    self._monitor_callback(oname, NDArray(outs[j], self._ctx))
        self.outputs = [NDArray(entry_vals[(id(n), x)], self._ctx)
                        for n, x in self._symbol._outputs]
        return self.outputs

    # -- backward ----------------------------------------------------------
    def backward(self, out_grads=None):
        """Compute gradients into ``args_grad``.

        Unsupplied head gradients default to zero — loss layers inject
        their own gradient via custom_vjp, matching the reference where
        ``SoftmaxOutput``'s backward ignores the head gradient entirely.
        """
        if not self._grad_names:
            return
        self._bwd_seen = True
        out_shapes = [o.shape for o in self.outputs] if self.outputs else None
        if out_shapes is None:
            raise MXNetError('call forward(is_train=True) before backward()')
        if out_grads is None and getattr(self, '_pending_grads', None) \
                is not None:
            # gradients were computed by the fused training forward
            grads = self._pending_grads
            self._pending_grads = None
            self._write_grads(grads)
            return
        if out_grads is None:
            cots = None   # zeros built inside the jitted program
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            if isinstance(out_grads, dict):
                out_grads = [out_grads[n] for n in self.output_names]
            cots = tuple(g.handle if isinstance(g, NDArray)
                         else jnp.asarray(g) for g in out_grads)
        self._dispatch_fwd_bwd()
        grad_args, other_args, aux = self._gathered_handles()
        with instrument.span('executor.backward', cat='executor'):
            outs, aux_upd, grads = self._jit_fwd_bwd(
                grad_args, other_args, aux, RANDOM.key,
                np.uint32(self._rng_seed), cots)
        self._write_grads(grads)

    def _write_grads(self, grads):
        """Write computed gradients into the bound grad arrays honoring
        grad_req write/add.  Under group2ctx the computation ran on the
        primary device; scatter each gradient back to its array's group
        device (the return leg of _CrossDeviceCopy)."""
        for name in self._grad_names:
            dst = self.grad_dict[name]
            g = grads[name]
            if self._group2ctx:
                g = jax.device_put(g, dst.context.jax_device)
            if self.grad_req[name] == 'add':
                dst._set_data(dst.handle + g)
            else:
                dst._set_data(g)

    def forward_backward(self, out_grads=None, **kwargs):
        """Fused step — ONE compiled program computes outputs and all
        gradients (the fast path used by Module.fit).

        The split ``forward(is_train=True); backward()`` API runs the
        same fused program at forward time (gradients cached for
        ``backward``), so neither entry point recomputes the forward;
        only ``backward(out_grads=...)`` with explicit head gradients
        pays a second program.
        """
        if not self._grad_names or self._monitor_callback is not None or \
                self._group2ctx:
            self.forward(is_train=True, **kwargs)
            self.backward(out_grads)
            return self.outputs
        for k, v in kwargs.items():
            src = v if isinstance(v, NDArray) else NDArray(jnp.asarray(v))
            self.arg_dict[k]._set_data(src.handle)
        self._last_is_train = True
        self._pending_grads = None
        self._dispatch_fwd_bwd()
        self._rng_seed += 1
        if out_grads is None:
            # loss-layer semantics: zero cotangents (built inside the
            # jitted program); custom_vjp loss ops inject their own
            # gradients
            cots = None
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            cots = tuple(g.handle if isinstance(g, NDArray)
                         else jnp.asarray(g) for g in out_grads)
        grad_args, other_args, aux = self._gathered_handles()
        with instrument.span('executor.forward_backward', cat='executor'):
            outs, aux_upd, grads = self._jit_fwd_bwd(
                grad_args, other_args, aux, RANDOM.key,
                np.uint32(self._rng_seed), cots)
        for name, val in aux_upd.items():
            self.aux_dict[name]._set_data(val)
        self.outputs = [NDArray(o, self._ctx) for o in outs]
        self._write_grads(grads)
        return self.outputs

    def _out_avals(self):
        if not hasattr(self, '_out_aval_cache'):
            graph_fn = _build_graph_fn(self._symbol, True, _count=False)
            args = {k: jax.ShapeDtypeStruct(v.shape, v.handle.dtype)
                    for k, v in self.arg_dict.items()}
            aux = {k: jax.ShapeDtypeStruct(v.shape, v.handle.dtype)
                   for k, v in self.aux_dict.items()}
            key = jax.ShapeDtypeStruct((2,), np.uint32)
            outs, aux_upd = jax.eval_shape(graph_fn, args, aux,
                                           jax.random.PRNGKey(0))
            self._out_aval_cache = (None,
                                    [(o.shape, o.dtype) for o in outs],
                                    None)
        return self._out_aval_cache

    def _dispatch_fwd_bwd(self):
        """The single home of retrace/cache-hit accounting for the fused
        fwd+bwd program: call exactly where ``_jit_fwd_bwd`` is about to
        run (backward() with pending grads runs nothing and must not
        count a hit)."""
        if not self._ensure_fwd_bwd():
            instrument.inc('executor.cache_hits')

    def _ensure_fwd_bwd(self):
        """Build the fused fwd+bwd program if needed.  Returns True when
        this call compiled it."""
        if self._jit_fwd_bwd is not None:
            return False
        instrument.inc('executor.retraces')
        prog_symbol = self._program_symbol(True)
        graph_fn = _build_graph_fn(prog_symbol, True)

        def fwd_bwd(grad_args, other_args, aux, key, seed, cotangents):
            # per-step key derivation INSIDE the program: an eager
            # fold_in per batch cost ~1ms of host dispatch on the
            # Module.fit path
            rng = jax.random.fold_in(key, seed)

            def f(ga):
                merged = dict(other_args)
                merged.update(ga)
                outs, aux_upd = graph_fn(merged, aux, rng)
                return outs, aux_upd

            (outs, aux_upd), vjp_fn = jax.vjp(mirror_wrap(f),
                                              dict(grad_args))
            if cotangents is None:
                # loss-layer semantics: zero head cotangents, built at
                # trace time instead of eagerly every batch
                cots_list = [jnp.zeros_like(o) for o in outs]
            else:
                cots_list = list(cotangents)
            grads = vjp_fn((cots_list,
                            jax.tree_util.tree_map(jnp.zeros_like,
                                                   aux_upd)))[0]
            return outs, aux_upd, grads

        self._jit_fwd_bwd = jax.jit(
            compile_cache.traced('fwd_bwd', prog_symbol, fwd_bwd))
        return True

    # -- misc API parity ---------------------------------------------------
    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self.arg_names]

    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self.arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self.aux_names]

    def set_monitor_callback(self, callback, pattern=None):
        """Install a per-tensor tap.  ``pattern`` (a compiled regex)
        restricts which intermediates are staged out of the compiled
        program; without it every node output is staged (reference
        semantics — the callback saw all names)."""
        self._monitor_callback = callback
        self._monitor_pattern = pattern

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for name, array in arg_params.items():
            if name in self.arg_dict:
                array.copyto(self.arg_dict[name])
            elif not allow_extra_params:
                raise ValueError('Find name "%s" that is not in the arguments'
                                 % name)
        if aux_params:
            for name, array in aux_params.items():
                if name in self.aux_dict:
                    array.copyto(self.aux_dict[name])
                elif not allow_extra_params:
                    raise ValueError('Find name "%s" that is not in the '
                                     'auxiliary states' % name)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        if arg_shapes is None:
            raise ValueError('Insufficient argument shapes provided.')
        new_args, new_grads, new_aux = {}, {}, {}
        for name, shape in zip(self.arg_names, arg_shapes):
            old = self.arg_dict[name]
            if shape == old.shape:
                new_args[name] = old
                if name in self.grad_dict:
                    new_grads[name] = self.grad_dict[name]
            else:
                new_args[name] = nd_zeros(shape, self._ctx,
                                          dtype=old.dtype)
                if name in self.grad_dict:
                    new_grads[name] = nd_zeros(shape, self._ctx,
                                               dtype=old.dtype)
        for name, shape in zip(self.aux_names, aux_shapes):
            old = self.aux_dict[name]
            new_aux[name] = old if shape == old.shape else \
                nd_zeros(shape, self._ctx, dtype=old.dtype)
        return Executor(self._symbol, self._ctx, new_args,
                        new_grads or None,
                        self.grad_req, new_aux, group2ctx=self._group2ctx)

    def debug_str(self):
        return self._symbol.debug_str()


def simple_bind(symbol: Symbol, ctx, grad_req='write', type_dict=None,
                group2ctx=None, shared_exec=None, **kwargs):
    """Allocate argument/grad/aux arrays from inferred shapes and bind
    (reference ``symbol.py:788``, ``MXExecutorBindEX``
    ``c_api_executor.cc:106``)."""
    arg_shapes, _, aux_shapes = symbol.infer_shape(**kwargs)
    if arg_shapes is None:
        raise ValueError('cannot infer shapes from %s' % kwargs)
    type_dict = type_dict or {}
    arg_names = symbol.list_arguments()
    aux_names = symbol.list_auxiliary_states()
    ctx = ctx if isinstance(ctx, Context) else Context(ctx)
    # honor per-variable ctx_group placement (AssignContext,
    # graph_executor.cc:225-314: every array lives on its group's device)
    var_ctx = {}
    if group2ctx:
        for node in symbol.topo_nodes():
            if node.is_variable:
                grp = node._extra_attr.get('ctx_group') or \
                    node._extra_attr.get('__ctx_group__')
                if grp and grp in group2ctx:
                    var_ctx[node.name] = group2ctx[grp]
    args = {n: nd_zeros(s, var_ctx.get(n, ctx),
                        dtype=type_dict.get(n, np.float32))
            for n, s in zip(arg_names, arg_shapes)}
    if isinstance(grad_req, str):
        req = {n: grad_req for n in arg_names}
    elif isinstance(grad_req, (list, tuple)):
        req = dict(zip(arg_names, grad_req))
    else:
        req = grad_req
    grads = {n: nd_zeros(s, var_ctx.get(n, ctx),
                         dtype=type_dict.get(n, np.float32))
             for n, s in zip(arg_names, arg_shapes)
             if req.get(n, 'null') != 'null'}
    aux = {n: nd_zeros(s, var_ctx.get(n, ctx))
           for n, s in zip(aux_names, aux_shapes)}
    return Executor(symbol, ctx, args, grads or None, req, aux,
                    group2ctx=group2ctx, shared_exec=shared_exec)
