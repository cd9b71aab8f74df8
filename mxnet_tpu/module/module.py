"""Module — symbol + executor group + optimizer wiring
(reference ``python/mxnet/module/module.py:323-567``).
"""
from __future__ import annotations

import logging

import numpy as np

from .. import context as ctx
from .. import instrument
from .. import ndarray as nd
from .. import optimizer as opt
from .. import symbol as sym
from ..base import MXNetError
from ..initializer import Uniform
from ..ndarray import NDArray, zeros
from ..optimizer import get_updater
from .base_module import BaseModule, _check_input_names
from .executor_group import DataParallelExecutorGroup


def _create_kvstore(kvstore, num_device, arg_params):
    """Decide kvstore + update_on_kvstore (reference model.py:40-77)."""
    update_on_kvstore = True
    if kvstore is None:
        kv = None
    elif isinstance(kvstore, str):
        if num_device == 1 and 'dist' not in kvstore:
            kv = None
        else:
            from .. import kvstore as kvs
            kv = kvs.create(kvstore)
            if kvstore == 'local':
                max_size = max(np.prod(param.shape)
                               for param in arg_params.values())
                if max_size > 1024 * 1024 * 16:
                    update_on_kvstore = False
    else:
        kv = kvstore
    if kv is None:
        update_on_kvstore = False
    return (kv, update_on_kvstore)


def _initialize_kvstore(kvstore, param_arrays, arg_params, param_names,
                        update_on_kvstore):
    """(reference model.py:79)"""
    for idx, param_on_devs in enumerate(param_arrays):
        kvstore.init(idx, arg_params[param_names[idx]])
        if update_on_kvstore:
            kvstore.pull(idx, param_on_devs, priority=-idx)


class Module(BaseModule):
    """(reference module.py:323)"""

    def __init__(self, symbol, data_names=('data',),
                 label_names=('softmax_label',), logger=logging,
                 context=None, work_load_list=None,
                 fixed_param_names=None, compute_dtype=None):
        super().__init__(logger=logger)
        # compute_dtype: optional mixed-precision dtype (e.g. jnp.bfloat16)
        # for the fused fit path; master params stay f32.
        self._compute_dtype = compute_dtype
        self._fused = None
        self._fused_trainable = None
        self._fused_frozen = None
        self._functional_opt = None
        self._fused_opt_state = None
        # dp×tp sharded-fit plan (docs/parallel.md): set by
        # fit(mesh=..., partition=...) / MXTPU_MESH via _set_parallel.
        # When active the fused step jits with NamedSharding in/out
        # shardings and the executor group places batches/params on the
        # mesh; _fused_shardings is the FitShardings actually baked
        # into the live fused program.
        self._mesh_plan = None
        self._fused_shardings = None
        self._fused_unavailable = False
        self._fused_just_built = False
        self._fused_metric_ref = None
        self._fused_metric_key = None
        # health sentinels folded into the fused step (health.py): the
        # fold key (action string or None) decides program reuse the
        # same way the metric fold key does; the ref is the per-fit
        # monitor whose device state the step threads
        self._fused_health_key = None
        self._aux_counted = None    # nodes with OpDef.aux_counters
        self._aux_shapes = None     # and their inputs' bound shapes
        self._health_ref = None
        # warm-start AOT executables for the fused step, keyed on the
        # batch signature (compile_cache.batch_sig); pending holds the
        # warmup pool's in-flight Futures for the same keys
        self._fused_aot = {}
        self._fused_aot_pending = {}
        # batch signatures whose perfwatch AOT capture failed — do not
        # re-attempt a lower() per step for them
        self._perf_aot_failed = set()
        if context is None:
            context = ctx.current_context()
        if isinstance(context, ctx.Context):
            context = [context]
        self._context = context
        if work_load_list is None:
            work_load_list = [1] * len(self._context)
        assert len(work_load_list) == len(self._context)
        self._work_load_list = work_load_list

        self._symbol = symbol
        data_names = list(data_names) if data_names is not None else []
        label_names = list(label_names) if label_names is not None else []
        arg_names = symbol.list_arguments()
        input_names = data_names + label_names
        self._param_names = [x for x in arg_names if x not in input_names]
        self._fixed_param_names = list(fixed_param_names or [])
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names = data_names
        self._label_names = label_names
        self._state_names = []
        self._output_names = symbol.list_outputs()

        _check_input_names(symbol, data_names, 'data', True)
        _check_input_names(symbol, label_names, 'label', False)
        _check_input_names(symbol, self._fixed_param_names, 'fixed_param', True)

        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False

        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None

        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None

    # -- persistence -------------------------------------------------------
    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """(reference module.py:97)"""
        from ..model import load_checkpoint
        symbol, arg_params, aux_params = load_checkpoint(prefix, epoch)
        mod = Module(symbol=symbol, **kwargs)
        mod._arg_params = arg_params
        mod._aux_params = aux_params
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = '%s-%04d.states' % (prefix, epoch)
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        """(reference module.py:123).  Every file commits atomically
        (resilience.atomic_replace) so a crash mid-checkpoint cannot
        leave a truncated file for auto-resume to trust."""
        from .. import instrument, resilience
        with resilience.atomic_replace('%s-symbol.json' % prefix) as tmp:
            self._symbol.save(tmp)
        param_name = '%s-%04d.params' % (prefix, epoch)
        self.save_params(param_name)
        instrument.inc('checkpoint.commits')
        logging.info('Saved checkpoint to "%s"', param_name)
        if save_optimizer_states:
            state_name = '%s-%04d.states' % (prefix, epoch)
            self.save_optimizer_states(state_name)
            logging.info('Saved optimizer state to "%s"', state_name)

    # -- properties --------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        outs = self._exec_group.get_outputs()
        if outs:
            return list(zip(self._output_names,
                            [o.shape for o in outs]))
        # no forward has run yet: infer from the symbol + bound shapes
        # (the reference read them off the bound executors at bind time,
        # executor_group.py; SequentialModule wiring relies on this)
        known = {name: shape for name, shape in
                 (self._data_shapes or []) + (self._label_shapes or [])}
        try:
            _, out_shapes, _ = self._symbol.infer_shape_partial(**known)
        except Exception:
            return []
        return list(zip(self._output_names, out_shapes or []))

    # -- params ------------------------------------------------------------
    def get_params(self):
        assert self.binded and self.params_initialized
        if self._params_dirty:
            self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False):
        """(reference module.py:193)"""
        if self.params_initialized and not force_init:
            return
        assert self.binded, 'call bind before initializing the parameters'

        if self._arg_params is None:
            self._arg_params = {
                name: zeros(shape, self._context[0])
                for name, shape in self._exec_group_param_shapes()}
        if self._aux_params is None:
            self._aux_params = {
                name: zeros(shape, self._context[0])
                for name, shape in self._exec_group_aux_shapes()}

        from ..initializer import InitDesc
        attrs = self._symbol.attr_dict()

        def _impl(name, arr, cache):
            desc = InitDesc(name, attrs.get(name))
            if cache is not None:
                if name in cache:
                    cache_arr = cache[name]
                    if cache_arr is not arr:
                        cache_arr.copyto(arr)
                else:
                    if not allow_missing:
                        raise RuntimeError('%s is not presented' % name)
                    if initializer is not None:
                        initializer(desc, arr)
            else:
                initializer(desc, arr)

        for name, arr in self._arg_params.items():
            _impl(name, arr, arg_params)
        for name, arr in self._aux_params.items():
            _impl(name, arr, aux_params)

        self.params_initialized = True
        self._params_dirty = False
        self._exec_group.set_params(self._arg_params, self._aux_params)

    def _exec_group_param_shapes(self):
        exec_ = self._exec_group.execs[0]
        return [(n, exec_.arg_dict[n].shape) for n in self._param_names
                if n in exec_.arg_dict]

    def _exec_group_aux_shapes(self):
        exec_ = self._exec_group.execs[0]
        return [(n, exec_.aux_dict[n].shape) for n in self._aux_names]

    def _sync_params_from_devices(self):
        self._exec_group.get_params(self._arg_params, self._aux_params)
        self._params_dirty = False

    # -- binding -----------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req='write'):
        """(reference module.py:388)"""
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning('Already binded, ignoring bind()')
            return

        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True

        if not for_training:
            assert not inputs_need_grad

        self._data_shapes = [x if isinstance(x, tuple) else tuple(x)
                             for x in data_shapes]
        self._data_shapes = [(n, tuple(s)) for n, s in data_shapes]
        self._label_shapes = [(n, tuple(s)) for n, s in label_shapes] \
            if label_shapes is not None else None

        shared_group = None
        if shared_module is not None:
            assert isinstance(shared_module, Module) and \
                shared_module.binded and shared_module.params_initialized
            shared_group = shared_module._exec_group

        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list,
            self._data_shapes, self._label_shapes, self._param_names,
            for_training, inputs_need_grad, shared_group,
            logger=self.logger, fixed_param_names=self._fixed_param_names,
            grad_req=grad_req, mesh_plan=self._mesh_plan)

        if shared_module is not None:
            self.params_initialized = True
            self._arg_params = shared_module._arg_params
            self._aux_params = shared_module._aux_params
        elif self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params)

    def _reset_bind(self):
        self.binded = False
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None
        self._fused = None
        self._fused_unavailable = False
        self._fused_aot = {}
        self._fused_aot_pending = {}

    def reshape(self, data_shapes, label_shapes=None):
        assert self.binded
        self._data_shapes = [(n, tuple(s)) for n, s in data_shapes]
        self._label_shapes = [(n, tuple(s)) for n, s in label_shapes] \
            if label_shapes is not None else None
        self._exec_group.reshape(self._data_shapes, self._label_shapes)
        if self._aux_counted:
            self._aux_shapes = self._aux_counted_shapes()

    # -- dp×tp sharded fit (docs/parallel.md) ------------------------------
    def _set_parallel(self, mesh, partition=None):
        """Install the dp×tp sharding plan for this module's fit path
        (``fit(mesh=..., partition=...)`` / MXTPU_MESH).  Changing the
        layout of an already-bound module rebinds it: the parameter
        arrays move to their mesh placement at the next bind (host
        copies are synced out first, so nothing trained is lost).  The
        plan is sticky across fits until replaced, like the context."""
        from ..parallel import mesh as _pmesh
        plan = _pmesh.make_plan(mesh, partition)
        if self._mesh_plan is not None and \
                plan.sig() == self._mesh_plan.sig():
            self._mesh_plan = plan
            return
        if self.binded:
            if self.params_initialized and self._params_dirty:
                self._sync_params_from_devices()
            self.logger.info(
                'mesh layout changed to %s: rebinding', plan.sig())
            self._reset_bind()
        if self.optimizer_initialized:
            # the optimizer wiring is layout-dependent (kvstore
            # demotion, update_on_kvstore, rescale_grad): force the
            # next fit's init_optimizer to re-derive it — otherwise a
            # store configured for the OLD layout keeps aggregating
            # (or refusing) under the new one.  Accumulated updater
            # momentum does not survive the layout change; resume from
            # a checkpoint to keep it.
            self.logger.info(
                'mesh layout changed: optimizer will re-initialize')
            self.optimizer_initialized = False
        self._mesh_plan = plan

    @property
    def _mesh_sig(self):
        """Mesh identity folded into AOT-table keys and warmup-manifest
        meta (None off the sharded path): the same batch avals compile
        to different executables per mesh shape/partition."""
        return self._mesh_plan.sig() if self._mesh_plan is not None \
            else None

    def _apply_dp_shrink(self, by=1):
        """Elastic repair of an ACTIVE mesh fit (docs/resilience.md):
        rebuild the mesh with the dp axis reduced by ``by``, re-derive
        the FitShardings/ZeRO placements for the new shape, and
        continue training mid-fit on the surviving width — the fused
        step re-AOTs through the warm-start pool at its next build
        instead of stalling the job.  Trained params are synced out
        first and re-placed on the new mesh; accumulated fused
        optimizer state does not survive the layout change (the
        ``_set_parallel`` contract).  Returns True when the shrink was
        applied; False (with the reason logged) when this module has
        no shrinkable mesh or the bound batch cannot divide the new
        dp."""
        from ..parallel import mesh as _pmesh
        plan = self._mesh_plan
        if plan is None or plan.dp - by < 1:
            return False
        spec = _pmesh.shrunk_spec(plan, by=by)
        if self.binded and \
                self._exec_group.batch_size % spec[_pmesh.DP_AXIS]:
            self.logger.warning(
                'elastic dp-shrink skipped: batch size %d does not '
                'divide the shrunk dp=%d — training continues on the '
                'old mesh %s', self._exec_group.batch_size,
                spec[_pmesh.DP_AXIS], plan.sig())
            return False
        mid_fit = self.binded and self.params_initialized
        if not mid_fit:
            self._set_parallel(spec, plan.partition)
            return True
        arg_params, aux_params = self.get_params()
        data_shapes, label_shapes = self._data_shapes, self._label_shapes
        optimizer, kvstore = self._optimizer, self._kvstore
        self._set_parallel(spec, plan.partition)     # unbinds, resets opt
        self.bind(data_shapes=data_shapes, label_shapes=label_shapes,
                  for_training=True)
        self.init_params(arg_params=arg_params, aux_params=aux_params,
                         force_init=True)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            force_init=True)
        instrument.inc('elastic.mesh_shrinks')
        instrument.set_gauge('elastic.mesh_dp',
                             float(self._mesh_plan.dp))
        self.logger.warning(
            'elastic dp-shrink: mesh rebuilt as %s — training '
            'continues at reduced width', self._mesh_plan.sig())
        return True

    def _elastic_pull_params(self):
        """Live-store param pull for a mid-job joiner (elastic
        re-seed): overwrite this module's params with the kv server's
        CURRENT master copy — fresher than any checkpoint.  Returns
        True when a pull happened (False on a demoted/absent data
        plane, where the compiled step owns the params)."""
        kv = self._kvstore
        if kv is None or getattr(kv, 'control_plane_only', False) or \
                'dist' not in getattr(kv, 'type', ''):
            return False
        exec_ = self._exec_group.execs[0]
        live = [(idx, name) for idx, name in
                enumerate(self._param_names) if name in exec_.arg_dict]
        kv.pull([i for i, _ in live],
                [[exec_.arg_dict[n]] for _, n in live])
        self._params_dirty = True
        return True

    # -- optimizer ---------------------------------------------------------
    def init_optimizer(self, kvstore='local', optimizer='sgd',
                       optimizer_params=(('learning_rate', 0.01),),
                       force_init=False):
        """(reference module.py:459)"""
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning('optimizer already initialized, '
                                'ignoring...')
            return

        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, len(self._context), self._arg_params)

        # kvstore demotion (docs/parallel.md): with a mesh active,
        # gradient reduction lives INSIDE the compiled step — a dist
        # store keeps only its control plane (barrier, telemetry,
        # elastic membership) and its data plane refuses loudly.  The
        # global batch is then the mesh's batch, not num_workers
        # times it.
        demoted = False
        if kvstore is not None and self._mesh_plan is not None and \
                'dist' in kvstore.type:
            demote = getattr(kvstore, 'demote_to_control_plane', None)
            if demote is not None:
                demote()
            update_on_kvstore = False
            demoted = True
            self.logger.info(
                'mesh %s active: dist kvstore %r demoted to control '
                'plane (gradients reduce inside the compiled step)',
                self._mesh_plan.sig(), kvstore.type)

        batch_size = self._exec_group.batch_size
        if kvstore and not demoted and 'dist' in kvstore.type and \
                '_sync' in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        if isinstance(optimizer, str):
            idx2name = {}
            if update_on_kvstore:
                idx2name.update(enumerate(self._exec_group.param_names))
            else:
                for i, n in enumerate(self._exec_group.param_names):
                    idx2name[i] = n
            optimizer_params = dict(optimizer_params)
            if 'rescale_grad' not in optimizer_params:
                optimizer_params['rescale_grad'] = rescale_grad
            optimizer = opt.create(optimizer, sym=self.symbol,
                                   param_idx2name=idx2name,
                                   **optimizer_params)
        else:
            assert isinstance(optimizer, opt.Optimizer)

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None
        self._fused = None
        self._fused_opt_state = None
        self._fused_unavailable = False
        self._fused_aot = {}
        self._fused_aot_pending = {}

        if kvstore and not demoted:
            # copy initialized params to the store (a demoted store
            # keeps no data plane — nothing to seed)
            param_arrays = [[self._exec_group.execs[0].arg_dict[n]]
                            for n in self._param_names]
            _initialize_kvstore(kvstore=kvstore, param_arrays=param_arrays,
                                arg_params=self._arg_params,
                                param_names=self._param_names,
                                update_on_kvstore=update_on_kvstore)
        if update_on_kvstore:
            kvstore.set_optimizer(self._optimizer)
        else:
            self._updater = get_updater(optimizer)
        self.optimizer_initialized = True

        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    # -- compute -----------------------------------------------------------
    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        self._exec_group.forward(data_batch, is_train)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._exec_group.backward(out_grads=out_grads)

    def forward_backward(self, data_batch):
        """Fused path: outputs + gradients from one compiled program,
        avoiding the forward recompute of the split fwd/bwd API."""
        assert self.binded and self.params_initialized
        self._exec_group.forward_backward(data_batch)

    def update(self):
        """(reference module.py:551 → model.py:88-131)"""
        assert self.binded and self.params_initialized and \
            self.optimizer_initialized
        self._params_dirty = True
        exec_ = self._exec_group.execs[0]
        # a control-plane-demoted store (mesh active) has no data
        # plane: the non-fused fallback updates locally, exactly like
        # the kvstore=None path — gradients are already globally
        # correct on the mesh.  Only the updater-available branch can
        # do that; an update_on_kvstore module holding a (shared,
        # externally) demoted store has no local updater, so it keeps
        # the store and lets its data plane raise the typed error.
        kvstore = self._kvstore
        if kvstore is not None and \
                getattr(kvstore, 'control_plane_only', False) and \
                not self._update_on_kvstore:
            kvstore = None
        # one list-push per batch: on a dist store the whole gradient
        # group crosses hosts as a single fused all-reduce
        # (DistKVStore.push -> allreduce_hosts_batch) instead of one
        # collective per parameter
        live = [(idx, name) for idx, name in
                enumerate(self._param_names) if name in exec_.grad_dict]
        idxs = [i for i, _ in live]
        grads = [[exec_.grad_dict[n]] for _, n in live]
        with instrument.span('module.update', cat='executor'):
            if self._update_on_kvstore:
                kvstore.push(idxs, grads)
                kvstore.pull(
                    idxs, [[exec_.arg_dict[n]] for _, n in live])
            else:
                if kvstore:
                    kvstore.push(idxs, grads)
                    kvstore.pull(idxs, grads)
                for idx, name in live:
                    self._updater(idx, exec_.grad_dict[name],
                                  exec_.arg_dict[name])

    # -- fused fit path ----------------------------------------------------
    def _device_metric(self, eval_metric):
        """The metric to fold into the fused step, or None when the
        numpy fallback applies (knob off, custom/np-only metric, legacy
        ``num``-sliced form, multi-output symbol)."""
        from .. import config
        if eval_metric is None or not config.get('MXTPU_DEVICE_METRICS'):
            return None
        if len(self._label_names) != 1 or len(self._output_names) != 1:
            return None
        capable = getattr(eval_metric, 'device_capable', None)
        if capable is None or not capable():
            return None
        return eval_metric

    def _fit_step(self, data_batch, eval_metric=None):
        """One fit-loop step: forward + backward + every parameter update
        as ONE compiled XLA program when the optimizer is functionally
        expressible — the TPU-native collapse of the reference's
        per-parameter kvstore push/pull + updater loop
        (``module.py:352-378`` here, ``model.py:88-131`` there).  When
        ``eval_metric`` has an on-device form (MXTPU_DEVICE_METRICS),
        its accumulator update is folded into the same program and the
        step returns True — the caller skips the host-side
        ``update_metric`` and the loop stays free of per-batch syncs.

        Falls back to ``forward_backward(); update()`` whenever fusion is
        inapplicable (dist kvstore, monitor installed, custom grad_req,
        non-functional optimizer, or ``MXTPU_FUSED_FIT=0``).

        Known deviations from the loop path: the scheduler sees the
        post-increment ``num_update`` for all parameters (the loop
        path's first index sees the pre-increment count — one boundary
        step at most); the local kvstore's internal weight copy is not
        maintained batch-by-batch (checkpoints and ``get_params`` read
        the executor, which is); and per-parameter gradients are never
        materialized into ``grad_dict`` — they live only inside the
        compiled program (install a monitor or set MXTPU_FUSED_FIT=0 to
        observe gradients).
        """
        from .. import perfwatch as _perfwatch
        # step_prep runs from here to the opening of dispatch: choosing
        # (or rebuilding) the compiled step, then _fused_call
        with _perfwatch.phase('step_prep'):
            from .. import health as _health
            metric = self._device_metric(eval_metric)
            mkey = metric.device_fold_key() if metric is not None else None
            hkey = _health.fold_key()
            if self._fused is not None and mkey == self._fused_metric_key \
                    and hkey == self._fused_health_key:
                # same folded computation (possibly a FRESH metric object —
                # fit() re-creates string metrics per call, and a fresh
                # health monitor per fit): reuse the compiled program, just
                # thread this fit's state objects
                self._fused_metric_ref = metric
                self._health_ref = _health.active_monitor()
            if self._fused is None and not self._fused_unavailable:
                self._try_build_fused(metric)
            elif self._fused is not None and \
                    (mkey != self._fused_metric_key or
                     hkey != self._fused_health_key):
                # a structurally different (or no) metric/health probe is
                # folded into the compiled step: rebuild for this one,
                # keeping optimizer state
                saved_state = self._fused_opt_state
                self._fused = None
                self._fused_unavailable = False
                self._try_build_fused(metric)
                if self._fused is not None and saved_state is not None:
                    self._fused_opt_state = saved_state
            elif self._fused is not None and self._functional_opt is not None \
                    and self._functional_opt.mult_signature != \
                    self._optimizer._mult_signature():
                # lr/wd multipliers changed (set_lr_mult after fit started):
                # they are baked into the compiled step, rebuild it but keep
                # the accumulated optimizer state (momentum etc.)
                saved_state = self._fused_opt_state
                self._fused = None
                self._fused_unavailable = False
                self._try_build_fused(metric)
                if self._fused is not None and saved_state is not None:
                    self._fused_opt_state = saved_state
            call = None if self._fused is None else \
                self._fused_call(data_batch, self._fused_metric_ref)
        if call is None:
            super()._fit_step(data_batch)
            return False
        self._fused_dispatch(data_batch, self._fused_metric_ref, call)
        return self._fused_metric_ref is not None

    def _try_build_fused(self, metric=None):
        from .. import config
        from ..parallel.train_step import make_fit_step
        self._fused_unavailable = True    # until proven otherwise
        # AOT executables compiled for a previous fused program are
        # stale the moment it is rebuilt
        self._fused_aot = {}
        self._fused_aot_pending = {}
        self._fused_shardings = None
        self._perf_aot_failed = set()
        if not config.get('MXTPU_FUSED_FIT'):
            return
        if not (self.binded and self.params_initialized and
                self.optimizer_initialized):
            return
        if self._kvstore is not None and 'dist' in self._kvstore.type \
                and self._mesh_plan is None:
            # a mesh-active fit keeps the fused step — the dist store
            # is demoted to control-plane only (init_optimizer)
            return
        exec_ = self._exec_group.execs[0]
        if exec_._monitor_callback is not None or exec_._group2ctx:
            return
        if self.inputs_need_grad:
            return
        if not isinstance(self._exec_group.grad_req_spec, str) or \
                self._exec_group.grad_req_spec != 'write':
            return
        trainable = [n for n in self._param_names if n in exec_.grad_dict]
        frozen = [n for n in self._param_names
                  if n not in exec_.grad_dict and n in exec_.arg_dict]
        indices = {n: i for i, n in enumerate(self._param_names)}
        functional = self._optimizer.make_functional(trainable, indices)
        if functional is None:
            return
        self._functional_opt = functional
        self._fused_trainable = trainable
        self._fused_frozen = frozen
        instrument.inc('executor.retraces')
        self._fused_just_built = True
        metric_fn = metric.device_delta_fn() if metric is not None \
            else None
        from .. import health as _health
        hmon = _health.active_monitor()
        # optimizer state is built BEFORE the step so the sharded path
        # can derive the exact per-leaf ZeRO shardings the jit bakes in
        params = {n: exec_.arg_dict[n].handle for n in trainable}
        opt_state = functional.init(params)
        shardings = None
        if self._mesh_plan is not None:
            shardings = self._build_fit_shardings(trainable, frozen,
                                                  exec_, opt_state)
            opt_state = self._place_opt_state(opt_state, shardings.opt)
        self._fused = make_fit_step(
            self._symbol, functional, data_names=self._data_names,
            compute_dtype=self._compute_dtype, metric_fn=metric_fn,
            metric_label=self._label_names[0] if metric_fn else None,
            metric_key=metric.device_fold_key()
            if metric is not None else None,
            health_action=hmon.action if hmon is not None else None,
            shardings=shardings)
        self._fused_shardings = shardings
        self._fused_metric_ref = metric
        self._fused_metric_key = metric.device_fold_key() \
            if metric is not None else None
        self._health_ref = hmon
        self._fused_health_key = hmon.action if hmon is not None else None
        self._fused_opt_state = opt_state
        self._overlay_updater_states()
        self._fused_unavailable = False
        if instrument.metrics_enabled() and self._aux_counted is None:
            self._aux_counted = self._find_aux_counted()
            if self._aux_counted:
                self._aux_shapes = self._aux_counted_shapes()
                instrument.add_device_source(self._aux_counter_source)

    def _find_aux_counted(self):
        """The nodes whose auxiliary states are counts
        (``OpDef.aux_counters``): for each the writer, the states' op-local
        and variable names, the node's attributes, its other inputs, and
        what the last drain saw."""
        found = []
        for n in self._symbol.topo_nodes():
            if n.is_variable or not n.opdef().aux_counters:
                continue
            local = n.opdef().aux_names(n.attrs)
            found.append((n.opdef().aux_counters, local,
                          [v.name for v, _ in n.inputs[-len(local):]],
                          n.opdef().canon_attrs(n.attrs),
                          n.inputs[:-len(local)], {}))
        return found

    def _aux_counted_shapes(self):
        """The shapes of those nodes' other inputs as bound now."""
        fed = [entries for *_, entries, _ in self._aux_counted]
        bound = {k: v.shape for k, v in
                 self._exec_group.execs[0].arg_dict.items()}
        shapes = iter(sym.Symbol([e for entries in fed for e in entries])
                      .infer_shape(**bound)[1])
        return [[next(shapes) for _ in entries] for entries in fed]

    def _aux_counter_source(self):
        """For the metric drain (``instrument.take_device_sources``): the
        counting auxiliary states as they stand after the last dispatched
        step, and the function that turns them into counters once the
        drain has waited for them."""
        if not self.binded or self._fused is None:
            return None
        aux = self._exec_group.execs[0].aux_dict
        arrays = [aux[name].handle for _, _, names, *_ in self._aux_counted
                  for name in names]

        def apply():
            for (write, local, names, attrs, _, seen), shapes in zip(
                    self._aux_counted, self._aux_shapes):
                now = {k: np.asarray(aux[name].handle)
                       for k, name in zip(local, names)}
                write(now, seen.get('before'), attrs, shapes)
                seen['before'] = now
        return arrays, apply

    def _build_fit_shardings(self, trainable, frozen, exec_, opt_state):
        """The exact sharding pytrees for this fused program: per-name
        trainable AND frozen parameter shardings (the executor group
        places both per the partition policy) and per-leaf optimizer
        shardings (ZeRO over dp, composed with the owning parameter's
        tp spec)."""
        import jax
        from ..parallel.mesh import FitShardings
        plan = self._mesh_plan
        param_sh = {n: plan.param_sharding(n, exec_.arg_dict[n].shape,
                                           dtype=exec_.arg_dict[n].dtype)
                    for n in trainable}
        frozen_sh = {n: plan.param_sharding(n, exec_.arg_dict[n].shape,
                                            dtype=exec_.arg_dict[n].dtype)
                     for n in frozen}
        plan.begin_opt_records(opt_state)
        opt_sh = {n: jax.tree_util.tree_map(
                      lambda leaf, n=n: plan.opt_leaf_sharding(
                          n, leaf.shape, dtype=leaf.dtype), sub)
                  for n, sub in opt_state.items()}
        # sharding inspector (docs/parallel.md): a parameter whose
        # requested tensor-parallel placement silently degraded to
        # replicated is now a recorded, warned-about fact — once per
        # fit, naming the params (tools/explain_sharding.py renders
        # the per-tensor reasons from plan.records_doc())
        plan.note_degraded(self.logger)
        return FitShardings(plan, param_sh, opt_sh, frozen=frozen_sh)

    def _place_opt_state(self, opt_state, opt_shardings):
        """Commit the optimizer state onto its ZeRO shardings (so each
        device holds only its 1/dp of every sharded leaf from step 0 —
        and the jit's in_shardings are met without a per-call
        reshard)."""
        import jax
        return {n: jax.tree_util.tree_map(jax.device_put, sub,
                                          opt_shardings[n])
                for n, sub in opt_state.items()}

    def _active_updater(self):
        if self._updater is not None:
            return self._updater
        if self._kvstore is not None:
            return getattr(self._kvstore, '_updater', None)
        return None

    def _overlay_updater_states(self):
        """Seed the fused optimizer state from preloaded Updater states.
        On the sharded path the overlaid leaves are re-committed onto
        their ZeRO shardings — a checkpoint-restored momentum ends up
        exactly where a never-restarted fit would hold it."""
        upd = self._active_updater()
        if upd is None or not upd.states:
            return
        overlaid = False
        for idx, name in enumerate(self._param_names):
            if name in self._fused_opt_state and idx in upd.states and \
                    upd.states[idx] is not None:
                self._fused_opt_state[name] = \
                    self._functional_opt.state_from_updater(
                        name, upd.states[idx])
                overlaid = True
        if overlaid and self._fused_shardings is not None:
            self._fused_opt_state = self._place_opt_state(
                self._fused_opt_state, self._fused_shardings.opt)

    def _sync_fused_states_to_updater(self):
        if self._fused_opt_state is None:
            return
        upd = self._active_updater()
        if upd is None:
            return
        for idx, name in enumerate(self._param_names):
            if name in self._fused_opt_state:
                upd.states[idx] = self._functional_opt.state_to_updater(
                    name, self._fused_opt_state[name])

    def fused_step_hlo(self):
        """The optimized HLO text of every fused fit step this module
        holds as a compiled executable, by batch signature: those of a
        warm start, and those the performance plane captured
        (``MXTPU_PERFWATCH``); a step that ran through ``jit`` alone is
        not held.  Every instruction's ``op_name`` carries the scopes
        ``<Operator>/<node>`` and ``forward_backward`` / ``optimizer`` /
        ``metric``, which is how a device trace's op events find their
        operator."""
        return {sig: aot.as_text() for sig, aot in self._fused_aot.items()}

    def fused_optimizer_state(self):
        """The fused fit step's optimizer state as the step holds it on
        the device: parameter name -> the optimizer's state of that
        parameter (Adam: ``(mean, variance)``).  None before the first
        fused step.  Read it; the next step donates these buffers."""
        return self._fused_opt_state

    def _run_fused(self, data_batch, metric=None):
        """One fused step on ``data_batch``: prepare, dispatch, commit."""
        from .. import perfwatch as _perfwatch
        with _perfwatch.phase('step_prep'):
            call = self._fused_call(data_batch, metric)
        self._fused_dispatch(data_batch, metric, call)

    def _fused_call(self, data_batch, metric):
        """Everything the host does for a fused step before the
        executable is called: placing the batch, the warm-start lookup,
        the update counts, the learning rate, the rng fold-in,
        assembling ``args`` (``perf.phase.step_prep``, opened by the
        caller).  Returns what :meth:`_fused_dispatch` takes."""
        import jax.numpy as jnp
        from .. import perfwatch as _perfwatch
        group = self._exec_group
        exec_ = group.execs[0]
        batch = {}
        for (name, _), value in zip(group.data_shapes, data_batch.data):
            v = value.handle if isinstance(value, NDArray) else \
                np.asarray(value)
            batch[name] = group._place_data(v)
        if group.label_shapes and data_batch.label:
            for (name, _), value in zip(group.label_shapes,
                                        data_batch.label):
                v = value.handle if isinstance(value, NDArray) else \
                    np.asarray(value)
                batch[name] = group._place_data(v)
        # warm-start lookup: an AOT executable pre-compiled for exactly
        # this batch signature runs without tracing the jit function at
        # all; a still-in-flight warmup for this signature is waited on
        # (it is compiling exactly what we need — waiting is strictly
        # cheaper than tracing it a second time on the hot path)
        aot = None
        sig = None
        # capture_on: the perf OR comm plane needs the AOT capture +
        # note_step path (collective accounting reads the compiled HLO)
        if self._fused_aot or self._fused_aot_pending or \
                _perfwatch.capture_on():
            from .. import compile_cache
            sig = compile_cache.batch_sig(batch, mesh=self._mesh_sig)
            aot = self._fused_aot.get(sig)
            if aot is None:
                fut = self._fused_aot_pending.get(sig)
                if fut is not None:
                    from .. import iowatch as _iowatch
                    with instrument.timed('compile.warmup_wait'), \
                            _iowatch.account('compile'):
                        try:
                            aot = fut.result()
                        except Exception:
                            aot = None
                else:
                    # a completion may land between the two reads
                    # (done-callback stores then pops): re-check the
                    # finished table before giving up on the warmup
                    aot = self._fused_aot.get(sig)
        params = {n: exec_.arg_dict[n].handle
                  for n in self._fused_trainable}
        frozen = {n: exec_.arg_dict[n].handle for n in self._fused_frozen}
        aux = {k: v.handle for k, v in exec_.aux_dict.items()}
        for idx, name in enumerate(self._param_names):
            if name in exec_.grad_dict:
                self._optimizer._update_count(idx)
        lr_t = jnp.float32(self._optimizer.host_lr())
        rng = exec_._next_rng()
        if self._fused_just_built:
            # this step's program was just compiled — already counted
            # as a retrace, not a cache hit
            self._fused_just_built = False
        else:
            instrument.inc('executor.cache_hits')
        health = self._health_ref if self._fused_health_key is not None \
            else None
        from .. import resilience
        if resilience.faults_on():
            # named fault site for the straggler story: a
            # MXTPU_FAULTS='fit.step:delay:P:SECS' plan slows THIS
            # rank's step cadence — what cluster.step_skew must name
            resilience.fault_point('fit.step')
        states = (params, frozen, aux, self._fused_opt_state)
        if metric is not None:
            states = states + (metric.device_state(),)
        if health is not None:
            states = states + (health.device_state(),)
        args = states + (batch, lr_t, rng)
        if aot is None and _perfwatch.capture_on() and \
                sig not in self._perf_aot_failed:
            # AOT-capture the program this step would jit anyway:
            # same lower+compile work (the trace still counts
            # executor.xla_traces), but through the AOT API the
            # executable exposes cost_analysis/memory_analysis —
            # the per-executable accounting the performance plane
            # and perf.mfu read
            from .. import iowatch as _iowatch
            try:
                # the same lower+compile the jit path would pay —
                # goodput charges it to the compile bucket
                with _iowatch.account('compile'), \
                        _perfwatch.phase('compile'):
                    aot = self._fused.lower(*args).compile()
            except Exception:
                self._perf_aot_failed.add(sig)
                aot = None
            else:
                _perfwatch.register_executable(
                    'fit_step', sig, aot,
                    num_devices=self._mesh_plan.num_devices
                    if self._mesh_plan is not None else 1)
                self._fused_aot[sig] = aot
        return [args, aot, sig, params, aux, health]

    def _fused_dispatch(self, data_batch, metric, call):
        """Call the executable (``perf.phase.dispatch``) and thread its
        results back into the module's state (``perf.phase.step_commit``:
        metric and health accumulators, parameters, aux states and
        outputs into the executor's arrays, letting go of the donated
        inputs, and — with the performance plane on — the memory
        ledger's and ``note_step``'s bookkeeping).  ``call`` is
        :meth:`_fused_call`'s list and is emptied: the step's inputs
        (some four hundred donated arrays for a ResNet-50) are then
        this frame's to release, inside ``step_commit``, and not
        whenever the caller's frame unwinds."""
        from .. import perfwatch as _perfwatch
        exec_ = self._exec_group.execs[0]
        args, aot, sig, params, aux, health = call
        del call[:]
        with instrument.span('module.fused_step', cat='executor'):
            try:
                with _perfwatch.phase('dispatch'):
                    if aot is not None:
                        try:
                            res = aot(*args)
                            instrument.inc('compile.aot_calls')
                        except Exception as exc:
                            if _perfwatch.is_oom(exc):
                                raise
                            # aval/sharding drift between warmup and
                            # the live call: drop the stale executable,
                            # take the jit path
                            self._fused_aot.pop(sig, None)
                            instrument.inc('compile.aot_fallbacks')
                            res = self._fused(*args)
                    else:
                        res = self._fused(*args)
            except Exception as exc:
                # RESOURCE_EXHAUSTED becomes a postmortem (top live
                # ledger entries + the executable's memory analysis)
                # instead of a bare stack trace
                _perfwatch.on_error(exc, 'fit_step', sig)
                raise
        with _perfwatch.phase('step_commit'):
            res = list(res)
            if health is not None:
                health.set_device_state(res.pop())
            if metric is not None:
                metric.set_device_state(res.pop())
            outs, new_params, new_aux, self._fused_opt_state = res
            if _perfwatch.enabled():
                # donated buffers (params/aux, donate_argnums 0/2) retire
                # from the memory ledger NOW — their finalizers later see
                # retired entries, so nothing double-counts
                for v in params.values():
                    _perfwatch.ledger_donate(v)
                for v in aux.values():
                    _perfwatch.ledger_donate(v)
                for o in outs:
                    _perfwatch.ledger_alloc('fit.outputs', o)
            if _perfwatch.capture_on():
                rows = data_batch.data[0].shape[0] if data_batch.data else 0
                _perfwatch.note_step('fit_step', sig, rows)
            for n, v in new_params.items():
                exec_.arg_dict[n]._set_data(v)
            for n, v in new_aux.items():
                exec_.aux_dict[n]._set_data(v)
            exec_.outputs = [NDArray(o, exec_._ctx) for o in outs]
            self._params_dirty = True
            del args, params, aux

    # -- warm-start compilation (docs/performance.md cold vs warm) ---------
    def _warm_start(self, eval_metric=None, data_sig=None):
        """AOT-compile the fused fit step BEFORE the first batch: the
        primary signature comes from the bound shapes (dtypes from the
        iterator's ``provide_signature`` when given, float32 otherwise)
        and any extra signatures from the warmup manifest recorded by a
        previous process for this symbol.  Non-blocking — lowering and
        XLA compilation run on the compile_cache warmup pool (with the
        persistent cache installed, the compile is a disk hit) and land
        in ``self._fused_aot``; ``_run_fused`` waits only when its
        exact signature is still in flight."""
        from .. import compile_cache
        from .. import metric as _metric_mod
        if not (self.binded and self.params_initialized and
                self.optimizer_initialized):
            return
        metric = None
        if eval_metric is not None:
            if not isinstance(eval_metric, _metric_mod.EvalMetric):
                eval_metric = _metric_mod.create(eval_metric)
            metric = self._device_metric(eval_metric)
        if self._fused is None and not self._fused_unavailable:
            self._try_build_fused(metric)
        if self._fused is None:
            return
        sigs = {}
        prim = {}
        for name, shape in (self._data_shapes or []):
            prim[name] = (tuple(shape), 'float32')
        for name, shape in (self._label_shapes or []):
            prim[name] = (tuple(shape), 'float32')
        # the iterator signature contributes DTYPES only — shapes come
        # from the bind (identical for the default bucket; for a
        # non-default BucketingModule bucket the signature's shapes
        # belong to the default bucket and would poison the key)
        for name, (_shape, dtype) in (data_sig or {}).items():
            if name in prim:
                prim[name] = (prim[name][0], str(dtype))
        if prim:
            sigs[compile_cache.sig_key(prim, mesh=self._mesh_sig)] = prim
        # manifest replay: batch signatures a previous run traced for
        # this exact symbol + folded metric + compute dtype + MESH
        # (e.g. a differently-padded final batch) — sharded executables
        # precompile and replay like single-chip ones, keyed on
        # (batch_sig, mesh_sig)
        fp = compile_cache.fingerprint(self._symbol)
        meta = compile_cache.jsonable(
            {'metric': self._fused_metric_key,
             'compute_dtype': (str(np.dtype(self._compute_dtype))
                               if self._compute_dtype is not None
                               else None),
             'health': self._fused_health_key,
             'mesh': self._mesh_sig})
        for entry in compile_cache.manifest_entries('fit_step', fp):
            if entry.get('meta') != meta or not entry.get('batch'):
                continue
            shapes = {name: (tuple(sd[0]), str(sd[1]))
                      for name, sd in entry['batch'].items()}
            sigs.setdefault(
                compile_cache.sig_key(shapes, mesh=self._mesh_sig),
                shapes)
        for sig, shapes in sigs.items():
            if sig in self._fused_aot or sig in self._fused_aot_pending:
                continue
            self._submit_warm_compile(sig, shapes)

    def _submit_warm_compile(self, sig, shapes):
        """Queue one ``lower().compile()`` of the fused step for the
        given batch signature on the warmup pool.  Lowering takes the
        LIVE param/aux/opt-state arrays (their avals and shardings are
        exactly what the loop will pass) and ShapeDtypeStructs with the
        executor group's data sharding for the batch — so the compiled
        executable is byte-identical to what the first jit call would
        have produced, and the persistent cache key matches across the
        AOT and jit paths."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import SingleDeviceSharding
        from .. import compile_cache
        exec_ = self._exec_group.execs[0]
        sharding = self._exec_group._data_sharding or \
            SingleDeviceSharding(self._context[0].jax_device)
        params = {n: exec_.arg_dict[n].handle
                  for n in self._fused_trainable}
        frozen = {n: exec_.arg_dict[n].handle for n in self._fused_frozen}
        aux = {k: v.handle for k, v in exec_.aux_dict.items()}
        batch = {name: jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype),
                                            sharding=sharding)
                 for name, (shape, dtype) in shapes.items()}
        metric = self._fused_metric_ref
        states = (params, frozen, aux, self._fused_opt_state)
        if metric is not None:
            states = states + (metric.device_state(),)
        if self._fused_health_key is not None and \
                self._health_ref is not None:
            states = states + (self._health_ref.device_state(),)
        args = states + (batch, jnp.float32(0.0),
                         jax.random.fold_in(nd.RANDOM.key, 0))
        fused = self._fused
        ndev = self._mesh_plan.num_devices \
            if self._mesh_plan is not None else 1
        # capture the TABLE OBJECTS, not self: a fused rebuild (metric
        # change, set_lr_mult, borrow_optimizer) invalidates by
        # reassigning fresh dicts — a late completion must land in the
        # orphaned table, never deliver the OLD program's executable
        # into the new one (same avals, silently wrong math)
        aot_table = self._fused_aot
        pending_table = self._fused_aot_pending

        def build():
            return fused.lower(*args).compile()

        fut = compile_cache.warmup_submit('fit_step', build)
        pending_table[sig] = fut

        def _done(f, sig=sig):
            # store BEFORE popping pending so a concurrent _run_fused
            # lookup can never miss both tables
            try:
                compiled = f.result()
                aot_table[sig] = compiled
            except Exception:
                instrument.inc('compile.warmup_errors')
            else:
                from .. import perfwatch
                if perfwatch.capture_on():
                    # per-executable XLA accounting for every warmed
                    # program (the fused step and, through the bucket
                    # modules' _warm_start, every declared bucket) —
                    # the comm plane's collective walk rides the same
                    # registration
                    perfwatch.register_executable('fit_step', sig,
                                                  compiled,
                                                  num_devices=ndev)
            finally:
                pending_table.pop(sig, None)
        fut.add_done_callback(_done)

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        return self._exec_group.get_outputs(merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and \
            self.inputs_need_grad
        return self._exec_group.get_input_grads(merge_multi_context)

    def update_metric(self, eval_metric, labels):
        self._exec_group.update_metric(eval_metric, labels)

    def _device_place_fn(self):
        if not self.binded or self._exec_group is None:
            return None
        return self._exec_group._place_data

    def install_monitor(self, mon):
        assert self.binded
        self._fused = None
        self._fused_unavailable = True
        self._fused_aot = {}
        self._fused_aot_pending = {}
        self._exec_group.install_monitor(mon)

    # -- optimizer state persistence --------------------------------------
    def save_optimizer_states(self, fname):
        """(reference module.py:672)"""
        assert self.optimizer_initialized
        self._sync_fused_states_to_updater()
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            from .. import resilience
            with resilience.atomic_replace(fname) as tmp:
                with open(tmp, 'wb') as fout:
                    fout.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        """(reference module.py:688)"""
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            with open(fname, 'rb') as f:
                self._updater.set_states(f.read())
        if self._fused is not None:
            self._overlay_updater_states()

    def borrow_optimizer(self, shared_module):
        """(reference module.py:701)"""
        assert shared_module.optimizer_initialized
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        self.optimizer_initialized = True
        # the fused step bakes in the optimizer's math — rebuild for the
        # borrowed one
        self._fused = None
        self._fused_opt_state = None
        self._fused_unavailable = False
        self._fused_aot = {}
        self._fused_aot_pending = {}
