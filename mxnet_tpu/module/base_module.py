"""BaseModule — the high-level train/predict interface
(reference ``python/mxnet/module/base_module.py``).

``fit`` reproduces the reference loop (``base_module.py:369-503``):
bind → init_params → init_optimizer → per-batch forward_backward / update /
update_metric, epoch-end evaluation, checkpoints.
"""
from __future__ import annotations

import logging
import time
from collections import namedtuple

import numpy as np

from .. import elastic as _elastic
from .. import instrument
from .. import iowatch as _iowatch
from .. import metric as _metric
from .. import io as _io
from .. import perfwatch as _perfwatch
from ..base import MXNetError

BatchEndParam = namedtuple('BatchEndParams',
                           ['epoch', 'nbatch', 'eval_metric', 'locals'])


def _as_list(obj):
    if isinstance(obj, list):
        return obj
    return [obj]


def _check_input_names(symbol, names, typename, throw):
    """(reference base_module.py:33)"""
    args = symbol.list_arguments()
    for name in names:
        if name in args:
            continue
        candidates = [arg for arg in args if
                      not arg.endswith('_weight') and
                      not arg.endswith('_bias') and
                      not arg.endswith('_gamma') and
                      not arg.endswith('_beta')]
        msg = "\033[91mYou created Module with Module(..., %s_names=%s) but " \
              "input with name '%s' is not found in symbol.list_arguments(). " \
              "Did you mean one of:\n\t%s\033[0m" % (
                  typename, str(names), name, '\n\t'.join(candidates))
        if throw:
            raise ValueError(msg)
        logging.warning(msg)


class BaseModule(object):
    """(reference base_module.py:64)"""

    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # -- high level API ----------------------------------------------------
    def forward_backward(self, data_batch):
        """(reference base_module.py:192)"""
        self.forward(data_batch, is_train=True)
        self.backward()

    def _fit_step(self, data_batch, eval_metric=None):
        """One training step of the fit loop.  Subclasses may fuse the
        whole step (forward+backward+update) into a single compiled
        program — Module does, see ``Module._fit_step``.  Returns truthy
        when the step ALSO accumulated ``eval_metric`` on device (the
        caller then skips the host-side ``update_metric``)."""
        from .. import health as _health
        mon = _health.active_monitor()
        if mon is not None:
            # sentinels ride the fused step only — a fit on this path
            # with them configured must say so, not silently report
            # healthy (one warning per fit)
            mon.warn_unfused()
        self.forward_backward(data_batch)
        self.update()
        return False

    def _device_place_fn(self):
        """Device placement function for the double-buffered feed
        (io.DeviceFeedIter), or None when this module has no bound
        device placement — Module overrides with the executor group's
        ``_place_data``."""
        return None

    def _set_parallel(self, mesh, partition=None):
        """Install a dp×tp sharding plan (``fit(mesh=...)``).  Module
        and BucketingModule implement it; other module types train on
        their own layout and say so instead of silently ignoring the
        request."""
        self.logger.warning(
            '%s does not implement fit(mesh=...): the mesh/partition '
            'request is ignored and training stays on the module\'s '
            'own device layout', type(self).__name__)

    def _step_ticket(self):
        """Arrays whose completion marks the last dispatched step —
        what engine.StepWindow waits on for backpressure."""
        try:
            return [out.handle for out in self.get_outputs()]
        except Exception:
            return None

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0):
        """Evaluate on eval_data (reference base_module.py:205)."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        if not isinstance(eval_metric, _metric.EvalMetric):
            eval_metric = _metric.create(eval_metric)
        eval_metric.reset()
        actual_num_batch = 0
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            self.update_metric(eval_metric, eval_batch.label)
            if batch_end_callback is not None:
                batch_end_params = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                                 eval_metric=eval_metric,
                                                 locals=locals())
                for callback in _as_list(batch_end_callback):
                    callback(batch_end_params)
            actual_num_batch += 1
        if score_end_callback:
            params = BatchEndParam(epoch=epoch, nbatch=actual_num_batch,
                                   eval_metric=eval_metric, locals=locals())
            for callback in _as_list(score_end_callback):
                callback(params)
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        """(reference base_module.py:262)"""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad] for out in self.get_outputs()]
            yield (outputs, nbatch, eval_batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        """(reference base_module.py:286)"""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        output_list = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad].copy()
                       for out in self.get_outputs()]
            output_list.append(outputs)
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                assert len(out) == num_outputs, \
                    'Cannot merge batches, as num of outputs is not the same ' \
                    'in mini-batches. Maybe bucketing is used?'
            from .. import ndarray as nd
            output_list2 = [nd.concatenate([out[i] for out in output_list])
                            for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    def fit(self, train_data, eval_data=None, eval_metric='acc',
            epoch_end_callback=None, batch_end_callback=None, kvstore='local',
            optimizer='sgd', optimizer_params=(('learning_rate', 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=None, arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, checkpoint_prefix=None, checkpoint_period=1,
            auto_resume=None, warm_start=None, mesh=None, partition=None):
        """Train (reference base_module.py:369-503).

        ``mesh`` (default: the MXTPU_MESH knob) turns on dp×tp
        multi-chip training (docs/parallel.md): a spec like ``'4x2'`` /
        ``'dp=4,tp=2'`` / ``8`` builds a ``('dp','tp')``
        ``jax.sharding.Mesh`` and the fused train step jits with
        NamedSharding in/out shardings — batch split over ``dp``,
        parameters per ``partition`` (default: the MXTPU_PARTITION
        knob; ``'replicated'`` or ``'auto'`` tensor parallelism),
        optimizer state ZeRO-sharded over ``dp``.  Gradient reductions
        happen inside the compiled program; a dist kvstore is demoted
        to control-plane duties only.

        ``warm_start`` (default: the MXTPU_WARM_START knob) pre-compiles
        the fused train step on background threads before the first
        batch — with MXTPU_COMPILE_CACHE set, from the persistent
        compilation cache a previous process populated (docs/
        performance.md "cold start vs warm start").

        ``checkpoint_prefix`` turns on atomic per-epoch checkpoints
        (``prefix-symbol.json`` + ``prefix-%04d.params`` every
        ``checkpoint_period`` epochs, committed tmp+fsync+rename).  With
        ``auto_resume`` (default: the MXTPU_AUTO_RESUME knob) a
        restarted process resumes from the newest LOADABLE checkpoint —
        truncated files from a crash are skipped by
        ``model.find_latest_checkpoint`` — instead of epoch 0: the
        recovery loop the reference drove manually with --load-epoch.
        """
        assert num_epoch is not None, 'please specify number of epochs'
        if initializer is None:
            from .. import initializer as _init
            initializer = _init.Uniform(0.01)

        # dp×tp sharded fit (docs/parallel.md): resolve the mesh /
        # partition knobs and install the plan BEFORE bind so the
        # executor group places batches and parameters on the mesh
        if mesh is None:
            from .. import config as _config
            mesh = _config.get('MXTPU_MESH') or None
        if partition is None:
            from .. import config as _config
            partition = _config.get('MXTPU_PARTITION') or None
        if mesh is not None:
            self._set_parallel(mesh, partition)

        auto_resumed = False
        if checkpoint_prefix:
            from ..model import find_latest_checkpoint, load_checkpoint
            if auto_resume is None:
                from .. import config as _config
                auto_resume = bool(_config.get('MXTPU_AUTO_RESUME'))
            if auto_resume:
                latest = find_latest_checkpoint(checkpoint_prefix)
                if latest is not None and latest > begin_epoch:
                    _, arg_params, aux_params = load_checkpoint(
                        checkpoint_prefix, latest)
                    begin_epoch = latest
                    force_init = True
                    auto_resumed = True
                    instrument.inc('checkpoint.resumes')
                    self.logger.info(
                        'Auto-resuming from checkpoint "%s-%04d.params"',
                        checkpoint_prefix, latest)

        # the time before the first step, a perf.setup.* span a call
        with _perfwatch.setup('bind'):
            self.bind(data_shapes=train_data.provide_data,
                      label_shapes=train_data.provide_label,
                      for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        with _perfwatch.setup('init_params'):
            self.init_params(initializer=initializer, arg_params=arg_params,
                             aux_params=aux_params,
                             allow_missing=allow_missing,
                             force_init=force_init)
        with _perfwatch.setup('init_optimizer'):
            self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                                optimizer_params=optimizer_params)

        if validation_metric is None:
            validation_metric = eval_metric
        if not isinstance(eval_metric, _metric.EvalMetric):
            eval_metric = _metric.create(eval_metric)

        # elastic self-healing plane (docs/resilience.md): arm the
        # membership coordinator on a store that speaks the protocol
        # (token-gated like the goodput ledger — a nested fit neither
        # owns nor closes the outer fit's coordinator).  A replacement
        # worker (MXTPU_ELASTIC_JOIN) re-seeds here: checkpoint
        # consensus + live-store pull, then enters the loop at the
        # cluster's current epoch instead of replaying the job.
        kv = getattr(self, '_kvstore', None)
        _el_token = _elastic.activate_fit(self, kv)
        try:
            if _el_token is not None and checkpoint_prefix:
                # initial ballot: a joiner's checkpoint consensus must
                # not wait for this rank's first commit to learn what
                # it holds
                _el_token.vote_checkpoints(checkpoint_prefix)
                if auto_resumed:
                    # the single-rank resume decision above ran before
                    # the kv existed: downgrade it to the cross-rank
                    # consensus when a peer never committed our newest
                    # epoch (a rank killed mid-save must not make the
                    # cluster train from divergent parameter eras)
                    begin_epoch = _elastic.reconcile_resume(
                        self, kv, checkpoint_prefix, begin_epoch)
            if kv is not None and \
                    getattr(kv, 'elastic_join_info', None) is not None:
                begin_epoch = _elastic.seed_joiner(self, kv,
                                                   checkpoint_prefix,
                                                   begin_epoch)

            # health sentinels (docs/observability.md): one fresh
            # monitor per fit, active BEFORE warm start so the
            # AOT-compiled fused step and the hot-loop one fold the
            # identical health probe.  Everything from here unwinds
            # through the deactivate below — a stale global monitor
            # must not leak into later fits/evals.
            from .. import health as _health
            _health.activate()
            # performance plane (docs/observability.md): re-read the
            # MXTPU_PERFWATCH/MXTPU_STEP_SAMPLE knobs and reset the
            # per-fit sampling cadence + steps/sec window
            _perfwatch.activate_fit()
            # input-pipeline & goodput plane (docs/observability.md):
            # open the wall-clock ledger on THIS thread — from here to
            # goodput_end below, every second is attributed (productive
            # remainder + exclusive badput buckets).  The token is None
            # when another fit's ledger is already live (nested/
            # concurrent fit): this fit then neither owns nor closes
            # it.
            _gp_token = _iowatch.activate_fit()
        except BaseException:
            # nothing below us opened yet: a failed re-seed/consensus/
            # plane activation must not leak the process-global
            # coordinator into every later fit (the finally below is
            # not open at this point)
            _elastic.deactivate_fit(_el_token)
            raise
        try:
            try:
                # warm-start compilation (docs/performance.md):
                # AOT-compile the fused step — and, for BucketingModule
                # under MXTPU_PRECOMPILE_BUCKETS, every declared bucket
                # — on the warmup pool NOW, overlapping XLA compilation
                # with the DeviceFeedIter spin-up instead of paying it
                # on the first batch
                if warm_start is None:
                    from .. import config as _config
                    warm_start = bool(_config.get('MXTPU_WARM_START'))
                if warm_start or getattr(self, '_warm_eager', False):
                    from .. import compile_cache
                    with _perfwatch.setup('warm_start'), \
                            _iowatch.account('compile'):
                        compile_cache.warm_start(self, eval_metric,
                                                 data_iter=train_data)

                # training loop.  If it unwinds with an error, leave
                # the dist store first (stop heartbeating): a
                # failed-but-alive process must read as dead to its
                # peers, or their end-of-fit barrier waits the full
                # MXTPU_KV_BARRIER_TIMEOUT for a rank that will never
                # arrive.
                try:
                    self._fit_epochs(train_data, eval_data, eval_metric,
                                     validation_metric,
                                     epoch_end_callback,
                                     batch_end_callback,
                                     eval_end_callback,
                                     eval_batch_end_callback, monitor,
                                     begin_epoch, num_epoch,
                                     checkpoint_prefix,
                                     checkpoint_period)
                except BaseException:
                    kv = getattr(self, '_kvstore', None)
                    if kv is not None and hasattr(kv, 'leave'):
                        try:
                            kv.leave()
                        except Exception:
                            pass
                    raise
            finally:
                # the skipped-step totals must reach the goodput ledger
                # before the per-fit monitor is torn down — only from
                # the fit that OWNS the ledger (a nested fit's monitor
                # must not overwrite the outer fit's health record)
                if _gp_token is not None:
                    _iowatch.note_health(_health.active_monitor())
                _health.deactivate()

            # end-of-fit rendezvous, dist_async ONLY: rank 0 hosts the
            # async server in-process, so a fast rank exiting early
            # would tear the server down under slower workers mid-epoch
            # (they survived that at the seed only when timing
            # aligned).  The barrier flushes this worker's pushes and
            # holds every rank until all LIVE workers finished — dead
            # ranks are excluded by the heartbeat timeout and the wait
            # is bounded by MXTPU_KV_BARRIER_TIMEOUT, so a crashed peer
            # cannot wedge exit.  dist_sync is excluded deliberately:
            # its barrier is an unbounded jax collective with no
            # dead-rank exclusion (and no co-located server to
            # protect), so a rendezvous there would trade nothing for a
            # hang risk.  Inside the ledger window: the wait lands in
            # the 'barrier' bucket (the client barrier accounts it).
            kv = getattr(self, '_kvstore', None)
            kv_type = getattr(kv, 'type', '')
            if kv is not None and 'dist' in kv_type and \
                    'async' in kv_type:
                kv.barrier()
        finally:
            # close + publish the goodput ledger even on an unwinding
            # fit — the flight recorder's postmortem then carries where
            # the failed run's time went.  Token-gated: only the fit
            # that OPENED the ledger closes it.
            if _gp_token is not None:
                _iowatch.goodput_end(_gp_token)
            _elastic.deactivate_fit(_el_token)

    def _fit_epochs(self, train_data, eval_data, eval_metric,
                    validation_metric, epoch_end_callback,
                    batch_end_callback, eval_end_callback,
                    eval_batch_end_callback, monitor, begin_epoch,
                    num_epoch, checkpoint_prefix, checkpoint_period):
        from .. import config as _config
        from ..engine import StepWindow
        # sync-free steady state (docs/performance.md): a bounded window
        # of dispatched steps, a double-buffered device feed, and (in
        # Module._fit_step) on-device metric accumulation.  Every piece
        # degrades to the synchronous path independently.
        window = StepWindow(_config.get('MXTPU_ASYNC_DEPTH'))
        feed = None
        if _config.get('MXTPU_DEVICE_FEED') and \
                not isinstance(train_data, _io.DeviceFeedIter):
            place = self._device_place_fn()
            if place is not None:
                train_data = feed = _io.DeviceFeedIter(train_data, place)
        try:
            self._fit_epochs_impl(
                train_data, eval_data, eval_metric, validation_metric,
                epoch_end_callback, batch_end_callback,
                eval_end_callback, eval_batch_end_callback, monitor,
                begin_epoch, num_epoch, checkpoint_prefix,
                checkpoint_period, window)
        finally:
            # hand the caller's iterator back in a clean state (the
            # feed runs one fetch ahead of the consumer)
            if feed is not None:
                feed.close()

    def _fit_epochs_impl(self, train_data, eval_data, eval_metric,
                         validation_metric, epoch_end_callback,
                         batch_end_callback, eval_end_callback,
                         eval_batch_end_callback, monitor, begin_epoch,
                         num_epoch, checkpoint_prefix, checkpoint_period,
                         window):
        fit_steps = 0       # iterations so far: the roots' step number
        for epoch in range(begin_epoch, num_epoch):
            tic = time.time()
            eval_metric.reset()
            nsamples = 0
            with instrument.span('fit.epoch[%d]' % epoch, cat='fit'):
                # one perf.fit_step root per iteration, from ASKING for
                # the batch to the last batch-end callback's return, so
                # consecutive roots tile the fit thread's time inside
                # the epoch (the ask that finds the iterator exhausted
                # is cancelled: the root's count is fit.batches')
                batches = iter(train_data)
                nbatch = 0
                while True:
                    with _perfwatch.fit_step(fit_steps) as root:
                        data_batch = next(batches, None)
                        if data_batch is None:
                            root.cancel()
                            break
                        # elastic actuation point (one global None check
                        # when off): raises on a coordinated abort or a
                        # fenced identity; blocks for the repair
                        # rendezvous — charged to the goodput ledger's
                        # 'recovery' bucket — when a rank was evicted
                        _elastic.step_check(self, epoch)
                        if monitor is not None:
                            monitor.tic()
                        # MXTPU_STEP_SAMPLE: every Nth step fully syncs
                        # after dispatch for an honest device-step
                        # latency (perf.step_latency) — exactly
                        # ceil(nbatch/N) extra syncs per epoch, none on
                        # unsampled steps
                        sampled = _perfwatch.sample_tick()
                        if sampled:
                            _samp_t0 = time.perf_counter()
                            _samp_ts = time.time_ns() // 1000
                        # a step that TRACED (cold jit — fused or
                        # fallback — or a shape-driven retrace) spent its
                        # wall time compiling, not training: the goodput
                        # ledger reattributes it to the 'compile' bucket,
                        # minus whatever nested account() regions (warmup
                        # waits, the perfwatch AOT capture) already
                        # claimed.  Two counter reads when nothing traced.
                        with instrument.span('fit.batch', cat='fit'), \
                                instrument.timed('fit.step'), \
                                _iowatch.traced_dispatch():
                            metric_on_device = self._fit_step(data_batch,
                                                              eval_metric)
                        window.admit(self._step_ticket())
                        if sampled:
                            # a deliberate measurement drain — same
                            # goodput bucket as the metric drains, so the
                            # exclusive-bucket invariant stays checkable
                            # against perf.host_syncs
                            with _iowatch.account('metric_drain'):
                                _perfwatch.sample_sync(self._step_ticket(),
                                                       _samp_t0, _samp_ts)
                        if instrument.metrics_enabled():
                            bs = data_batch.data[0].shape[0] \
                                if data_batch.data \
                                else getattr(train_data, 'batch_size', 0)
                            # pad rows are replicated filler, not samples
                            bs -= getattr(data_batch, 'pad', 0) or 0
                            nsamples += bs
                            instrument.inc('fit.batches')
                            instrument.inc('fit.samples', bs)
                        if not metric_on_device:
                            self.update_metric(eval_metric,
                                               data_batch.label)
                        if monitor is not None:
                            monitor.toc_print()
                        if batch_end_callback is not None:
                            with _perfwatch.phase('callbacks'):
                                batch_end_params = BatchEndParam(
                                    epoch=epoch, nbatch=nbatch,
                                    eval_metric=eval_metric,
                                    locals=locals())
                                for callback in _as_list(
                                        batch_end_callback):
                                    callback(batch_end_params)
                    nbatch += 1
                    fit_steps += 1

                # everything an epoch's end runs, outside any root:
                # the window drain, the metric log, the parameters'
                # round trip to the host, the checkpoint, the
                # epoch-end callbacks
                with _perfwatch.phase('epoch_end'):
                    # the epoch boundary is a real barrier: wait out
                    # every step still in the async window before
                    # timing/logging
                    window.drain()
                    # one epoch of training is finished
                    for name, val in eval_metric.get_name_value():
                        self.logger.info('Epoch[%d] Train-%s=%f',
                                         epoch, name, val)
                    if instrument.profiling_enabled():
                        # an honest epoch time needs the device drained
                        # — async dispatch otherwise under-reports
                        # (engine.sync doubles as the WaitForAll wait
                        # span at the epoch boundary).  Gated on
                        # PROFILING, not metrics: metrics-only mode stays
                        # passive — no injected blocking round-trip — at
                        # the cost of an epoch timer that can
                        # under-report the last step's un-drained tail
                        from ..engine import sync as _engine_sync
                        _engine_sync(None)
                    toc = time.time()
                    if instrument.metrics_enabled() and toc > tic:
                        instrument.set_gauge('fit.samples_per_sec',
                                             nsamples / (toc - tic))
                        instrument.observe('fit.epoch', toc - tic)
                    self.logger.info('Epoch[%d] Time cost=%.3f', epoch,
                                     (toc - tic))

                    # sync aux params across devices
                    arg_params_, aux_params_ = self.get_params()
                    self.set_params(arg_params_, aux_params_)

                    if checkpoint_prefix and (
                            (epoch + 1) % checkpoint_period == 0
                            or epoch + 1 == num_epoch):
                        from ..model import save_checkpoint as _save_ckpt
                        with _iowatch.account('checkpoint'):
                            _save_ckpt(checkpoint_prefix, epoch + 1,
                                       self.symbol, arg_params_,
                                       aux_params_)
                            # keep this rank's ckpt_vote current so a
                            # joiner's consensus never trusts a stale
                            # ballot
                            _elastic.note_checkpoint(checkpoint_prefix)

                    if epoch_end_callback is not None:
                        for callback in _as_list(epoch_end_callback):
                            callback(epoch, self.symbol, arg_params_,
                                     aux_params_)

            # evaluation on validation set
            if eval_data:
                with _iowatch.account('eval'):
                    res = self.score(
                        eval_data, validation_metric,
                        score_end_callback=eval_end_callback,
                        batch_end_callback=eval_batch_end_callback,
                        epoch=epoch)
                for name, val in res:
                    self.logger.info('Epoch[%d] Validation-%s=%f',
                                     epoch, name, val)

            # end of 1 epoch, reset the data-iter for another epoch
            train_data.reset()

    # -- symbol ------------------------------------------------------------
    @property
    def symbol(self):
        return self._symbol

    # -- abstract interface ------------------------------------------------
    def get_params(self):
        raise NotImplementedError()

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False):
        raise NotImplementedError()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)

    def save_params(self, fname):
        """(reference base_module.py:557).  Atomic commit: a crash
        mid-write leaves the previous file, never a truncated one."""
        arg_params, aux_params = self.get_params()
        save_dict = {('arg:%s' % k): v for k, v in arg_params.items()}
        save_dict.update({('aux:%s' % k): v for k, v in aux_params.items()})
        from .. import ndarray as nd
        from .. import resilience
        with resilience.atomic_replace(fname) as tmp:
            nd.save(tmp, save_dict)

    def load_params(self, fname):
        """(reference base_module.py:570)"""
        from .. import ndarray as nd
        save_dict = nd.load(fname)
        arg_params = {}
        aux_params = {}
        for k, value in save_dict.items():
            arg_type, name = k.split(':', 1)
            if arg_type == 'arg':
                arg_params[name] = value
            elif arg_type == 'aux':
                aux_params[name] = value
            else:
                raise ValueError('Invalid param file ' + fname)
        self.set_params(arg_params, aux_params)

    def get_states(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        assert not merge_multi_context
        return []

    def set_states(self, states=None, value=None):
        assert self.binded and self.params_initialized
        assert not states and not value

    def install_monitor(self, mon):
        raise NotImplementedError()

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req='write'):
        raise NotImplementedError()

    def init_optimizer(self, kvstore='local', optimizer='sgd',
                       optimizer_params=(('learning_rate', 0.01),),
                       force_init=False):
        raise NotImplementedError()

    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError()

    @property
    def data_names(self):
        raise NotImplementedError()

    @property
    def output_names(self):
        raise NotImplementedError()

    @property
    def data_shapes(self):
        raise NotImplementedError()

    @property
    def label_shapes(self):
        raise NotImplementedError()

    @property
    def output_shapes(self):
        raise NotImplementedError()
