"""Multi-model serving fleet on the Predictor/AOT substrate.

:class:`ModelServer` holds a registry of named models, each served by
N **replicas** — Predictors over DISJOINT device sets (submeshes carved
from the local devices: replica ``r`` of a ``mesh='dp=1,tp=2'`` model
owns local devices ``[2r, 2r+1]``; unsharded replicas own device ``r``)
— behind ONE shared admission queue with per-replica
:class:`~mxnet_tpu.serving.batcher.DynamicBatcher` workers.  The server
is the traffic-facing layer over the same optimized executor stack the
trainer uses — serving is a deployment mode of the runtime, not a
separate system.

- **tp-sharded models**: ``load_model(..., mesh='dp=1,tp=2',
  partition='auto')`` builds sharded Predictors (per-pow2-bucket AOT
  executables with explicit NamedSharding in/out, keyed on the compile
  plane's ``(batch_sig, mesh_sig)`` signature) so models too big for
  one chip serve tensor-parallel; per-tensor degradation reasons land
  in the sharding-inspector records (``Predictor.sharding_records``).
- **replica fleet**: :meth:`scale_up` / :meth:`scale_down` grow and
  shrink the replica set while traffic flows — a new replica's pow2
  buckets are pre-compiled on the compile-cache warmup pool BEFORE its
  worker attaches (it never cold-compiles on the serving path), and a
  removed replica drains its in-flight flush at a flush boundary.
  Scaling decisions, load/unload/reload all serialize on the per-model
  admin lock, so an autoscaler can never race a hot swap.
- **load/unload/reload are hot**: models are added and replaced while
  traffic flows.  A reload builds every replica's replacement Predictor
  BEFORE swapping, then swaps each under its replica lock between
  flushes — an in-flight batch drains on the OLD executable, the next
  flush runs the new one (``serving.reloads``).  Unload drains (or
  fails) the queue and stops the workers.
- **admission + SLO**: the per-model, per-lane queue bound sheds with
  :class:`ServerOverloadedError`; queue-wait / execute / e2e latency
  land in ``serving.*_secs`` histograms (p50/p95/p99) — the model-wide
  plain series plus labeled per-replica/per-lane series
  (``|model=m,replica=r`` — ``instrument.render_prometheus`` exposes
  them as real Prometheus labels, so a hot replica is attributable,
  not averaged away).
- **autoscaling**: :meth:`autoscale` enrolls a model with the
  closed-loop :class:`~mxnet_tpu.serving.autoscaler.ReplicaAutoscaler`
  (windowed p99 vs the SLO; docs/serving.md).
"""
from __future__ import annotations

import threading
import time

import numpy as np

from .. import config, instrument, resilience
from .. import model as model_mod
from ..base import MXNetError
from ..context import current_context
from ..predictor import Predictor
from .batcher import (DeadlineExceededError, DynamicBatcher,
                      ReplicaQuarantinedError, ServerOverloadedError)

__all__ = ['ModelServer', 'ModelNotFoundError', 'ServerOverloadedError',
           'DeadlineExceededError', 'ReplicaQuarantinedError']


class ModelNotFoundError(MXNetError):
    """No model with that name is loaded."""


class _Replica(object):
    """One serving replica: a live Predictor on its own device set
    behind a lock (flush vs reload swap), plus the slot index its
    devices were carved from."""
    __slots__ = ('rid', 'predictor', 'lock')

    def __init__(self, rid, predictor):
        self.rid = rid
        self.predictor = predictor
        self.lock = threading.Lock()


class _Model(object):
    """One registry entry: the replica set, the shared batcher, the
    builder kwargs replicas are re-built from, and the ADMIN lock that
    serializes every lifecycle mutation (load/unload/reload/scale) —
    the autoscaler and a maintenance unload contend here, not on the
    flush path."""
    __slots__ = ('name', 'replicas', 'batcher', 'generation',
                 'admin_lock', 'build_kw', 'closed')

    def __init__(self, name):
        self.name = name
        self.replicas = []
        self.batcher = None
        self.generation = 0
        self.admin_lock = threading.RLock()
        self.build_kw = None
        self.closed = False

    @property
    def predictor(self):
        """Replica 0's Predictor — the single-replica compat view."""
        return self.replicas[0].predictor if self.replicas else None


class ModelServer(object):
    """Dynamic-batching model server over named Predictors.

    >>> server = ModelServer()
    >>> server.load_model('clf', prefix='/ckpt/clf', epoch=3,
    ...                   input_shapes={'data': (1, 8)})
    >>> probs = server.predict('clf', data=np.zeros((1, 8)))[0]

    ``predict`` blocks on the response future; ``submit`` returns it.
    Per-request outputs are numpy arrays sliced to the request's rows.
    """

    def __init__(self, max_delay_ms=None, max_batch=None, max_queue=None,
                 dev_type=None, dev_id=0):
        self._max_delay_ms = max_delay_ms
        self._max_batch = max_batch
        self._max_queue = max_queue
        # no dev_type: the default context's, i.e. the accelerator where
        # one answers (context.current_context)
        self._dev = (dev_type or current_context().device_type, dev_id)
        self._models = {}
        self._lock = threading.Lock()
        self._closed = False
        self._autoscaler = None
        self._supervisor = None

    # -- replica device carving ---------------------------------------------

    def _capacity_for(self, entry):
        """Replica capacity from an entry already in hand — the ONE
        home of the rule (the autoscaler calls this with the entry it
        holds, so a registry re-lookup cannot race the model's own
        unload mid-decision)."""
        mesh = (entry.build_kw or {}).get('mesh')
        if mesh is None:
            return 1 << 30
        from ..parallel.mesh import submesh_capacity
        return max(1, submesh_capacity(mesh))

    def replica_capacity(self, name):
        """How many replicas the local device set can hold for ``name``
        (the autoscaler's hard ceiling).  Sharded models need DISJOINT
        submeshes (``mesh.submesh_capacity``).  Unsharded models are
        unbounded here (replicas past the device count share devices
        round-robin and still buy pipeline overlap) — the autoscaler's
        ``max_replicas`` is the governing cap."""
        return self._capacity_for(self._entry(name))

    def _replica_devices(self, mesh, slot):
        """The device set of replica slot ``slot``: a disjoint submesh
        (``mesh.carve_submesh_devices``) for sharded models; unsharded
        models get device ``slot`` (wrapping only when the host has
        fewer devices than replicas — a CPU dev box, where replicas
        still buy pipeline overlap)."""
        if mesh is None:
            import jax
            n = max(1, len(jax.devices()))
            # replica 0 stays on the server's CONFIGURED device; later
            # slots walk the device list from there
            return None, (self._dev[0],
                          (int(self._dev[1]) + int(slot)) % n)
        from ..parallel.mesh import carve_submesh_devices
        try:
            devs = carve_submesh_devices(mesh, slot)
        except ValueError as e:
            raise MXNetError(str(e))
        return devs, self._dev

    # -- registry -----------------------------------------------------------

    def _build_predictor(self, prefix=None, epoch=None, symbol_json=None,
                         params=None, input_shapes=None, output_keys=None,
                         mesh=None, partition=None, slot=0):
        if input_shapes is None:
            raise MXNetError('input_shapes is required')
        if prefix is not None:
            if epoch is None:
                epoch = model_mod.find_latest_checkpoint(prefix)
                if epoch is None:
                    raise MXNetError('no loadable checkpoint at %r'
                                     % prefix)
            with open('%s-symbol.json' % prefix) as f:
                symbol_json = f.read()
            from .. import ndarray as nd
            params = nd.load('%s-%04d.params' % (prefix, epoch))
        if symbol_json is None or params is None:
            raise MXNetError('need prefix= or symbol_json= + params=')
        devices, dev = self._replica_devices(mesh, slot)
        return Predictor(symbol_json, params, dict(input_shapes),
                         dev_type=dev[0], dev_id=dev[1],
                         output_keys=output_keys, pad_to_bucket=True,
                         mesh=mesh, partition=partition, devices=devices)

    def load_model(self, name, prefix=None, epoch=None, symbol_json=None,
                   params=None, input_shapes=None, output_keys=None,
                   predictor=None, warm_start=None, replicas=None,
                   mesh=None, partition=None):
        """Register ``name`` and start its batcher.  Source is either a
        checkpoint ``prefix`` (+ optional ``epoch``; latest loadable
        otherwise), raw ``symbol_json`` + ``params``, or a prebuilt
        ``predictor`` (tests, custom wrappers; pass a LIST of
        predictors for a prebuilt multi-replica fleet).  ``replicas``
        (default ``MXTPU_SERVE_REPLICAS``) starts that many replicas on
        disjoint device sets; ``mesh``/``partition`` serve each replica
        tensor-parallel (``Predictor(mesh=...)``)."""
        import re
        if not re.fullmatch(r'[A-Za-z0-9._:-]+', str(name)):
            # the name is interpolated into the |key=value labeled
            # metric convention and the Prometheus exposition: label
            # metacharacters (| , = ") would forge labels downstream
            raise MXNetError(
                'model name %r must match [A-Za-z0-9._:-]+ (it becomes '
                'a metric label)' % (name,))
        reserved = {'name', 'priority', 'timeout', 'deadline_ms',
                    'self'} & set(input_shapes or {})
        if reserved:
            # submit()/predict() consume these keyword names for the
            # lane selector, the blocking timeout, and the request
            # deadline — an input so named could never be passed
            # through **inputs
            raise MXNetError(
                'input name(s) %s collide with submit()/predict() '
                'keywords; rename the model inputs'
                % sorted(reserved))
        if replicas is None:
            replicas = int(config.get('MXTPU_SERVE_REPLICAS'))
        replicas = max(1, int(replicas))
        build_kw = dict(prefix=prefix, epoch=epoch,
                        symbol_json=symbol_json, params=params,
                        input_shapes=input_shapes,
                        output_keys=output_keys, mesh=mesh,
                        partition=partition)
        prebuilt = None
        if predictor is not None:
            prebuilt = list(predictor) if isinstance(
                predictor, (list, tuple)) else [predictor]
            if len(prebuilt) > replicas:
                raise MXNetError(
                    'more prebuilt predictors (%d) than replicas (%d)'
                    % (len(prebuilt), replicas))
            if len(prebuilt) < replicas and symbol_json is None and \
                    prefix is None:
                raise MXNetError(
                    'prebuilt predictor count (%d) < replicas (%d) '
                    'and no builder source given'
                    % (len(prebuilt), replicas))
        with self._lock:
            if self._closed:
                raise MXNetError('server is closed')
            if name in self._models:
                raise MXNetError('model %r already loaded (use '
                                 'reload_model)' % name)
        # build the WHOLE fleet before publishing the entry: a predict
        # racing a slow (warm-compiling) load must see a typed
        # ModelNotFoundError, never a half-constructed model
        entry = _Model(name)
        entry.build_kw = build_kw
        try:
            with entry.admin_lock:
                first = prebuilt[0] if prebuilt else \
                    self._build_predictor(slot=0, **build_kw)
                rep0 = _Replica(0, first)
                entry.replicas.append(rep0)
                entry.batcher = DynamicBatcher(
                    name,
                    self._make_execute(rep0),
                    max_delay_ms=self._max_delay_ms,
                    max_batch=self._max_batch,
                    max_queue=self._max_queue,
                    batch_inputs=first._batch_inputs)
                if warm_start is None:
                    warm_start = bool(config.get('MXTPU_WARM_START'))
                if warm_start:
                    self._warm_replica(entry, rep0, wait=False)
                for slot in range(1, replicas):
                    pre = prebuilt[slot] if prebuilt and \
                        slot < len(prebuilt) else None
                    self._add_replica(entry, slot, predictor=pre,
                                      warm=warm_start)
        except Exception:
            if entry.batcher is not None:
                entry.batcher.stop(drain=False)
            raise
        with self._lock:
            if self._closed:
                entry.batcher.stop(drain=False)
                raise MXNetError('server is closed')
            if name in self._models:
                entry.batcher.stop(drain=False)
                raise MXNetError('model %r already loaded (use '
                                 'reload_model)' % name)
            self._models[name] = entry
        self._note_models()
        self._note_replicas(entry)
        if config.get('MXTPU_SERVE_SUPERVISE'):
            # opt-in auto-enrollment: the supervision plane costs
            # nothing (no thread, no request-path work) unless this
            # knob — or an explicit supervise() call — turns it on
            self.supervise(name)
        return entry.predictor

    def _note_models(self):
        with self._lock:
            instrument.set_gauge('serving.models', len(self._models))

    def _note_replicas(self, entry):
        instrument.set_gauge('serving.replicas|model=%s' % entry.name,
                             len(entry.replicas))

    def _make_execute(self, rep):
        site_op = 'r%s' % rep.rid

        def _execute(inputs, rows):
            """Batcher hook: run the merged batch through THIS
            replica's CURRENT Predictor.  The replica lock alone orders
            the flush against reload swaps and warm-up forwards — the
            predictor captured here serves this whole batch even if a
            reload lands mid-execute."""
            with rep.lock:
                if resilience.faults_on():
                    # per-replica chaos site: 'serve.execute.r<id>'
                    # (inside the lock, so an injected delay occupies
                    # the replica exactly like a slow model would)
                    resilience.fault_point('serve.execute', op=site_op)
                predictor = rep.predictor
                predictor.forward(**inputs)
                outs = [predictor.get_output(i)
                        for i in range(predictor.num_outputs)]
            bucket = getattr(predictor, '_active_bucket', None)
            if bucket is not None:
                # the flush-composition record (servewatch) names the
                # pow2 bucket this batch actually rode and a stable
                # executable signature for it
                _execute.last_info = (
                    bucket, '%s[b=%d]' % (type(predictor).__name__,
                                          bucket))
            return outs
        _execute.last_info = None
        return _execute

    def _pow2_buckets(self, max_batch):
        from .. import compile_cache
        buckets, b = [], 1
        while b < max_batch:
            buckets.append(b)
            b <<= 1
        buckets.append(compile_cache.pad_to_bucket(max_batch))
        return buckets

    def _warm_replica(self, entry, rep, wait=True, timeout=300):
        """Pre-compile every pow2 bucket executor of one replica on the
        compile-cache warmup pool.  ``wait=True`` blocks until the
        buckets are compiled: the scale-up path uses it so a NEW
        replica never cold-compiles on the serving path."""
        predictor = rep.predictor

        def guard(fn):
            # serialize the warm forward with this replica's flushes
            # (a plain Predictor's executor state is not thread-safe)
            # and skip if a reload swapped the predictor under us
            with rep.lock:
                return fn() if rep.predictor is predictor else None
        return self._warm_predictor(entry, predictor, rep.rid,
                                    wait=wait, timeout=timeout,
                                    guard=guard)

    def _warm_predictor(self, entry, predictor, tag, wait=True,
                        timeout=300, guard=None):
        """Warm one Predictor's pow2 buckets on the compile-cache
        warmup pool (sharded Predictors compile their AOT bucket
        executables; unsharded ones forward zeros through each bucket —
        with the persistent cache installed these hit disk).  Also the
        reload path's pre-swap warm-up, where the replacement is not
        attached to any replica yet (``guard`` None — nothing else can
        touch it)."""
        from .. import compile_cache
        compile_cache.ensure_persistent_cache()
        # warm to the CONFIGURED cap, not the live max_batch: a replica
        # added while the autoscaler has the batch transiently shrunk
        # must not cold-compile the larger buckets after restore_batch
        max_batch = getattr(entry.batcher, 'configured_max_batch',
                            entry.batcher.max_batch)
        warm = getattr(predictor, 'warm_buckets', None)
        futs = warm(max_batch) if warm is not None else []
        if not futs:
            shapes = getattr(predictor, '_input_shapes', None)
            batch_inputs = getattr(predictor, '_batch_inputs', None)
            if not shapes or not batch_inputs:
                return []

            def warm_bucket(bucket):
                def fwd():
                    zeros = {
                        k: np.zeros((bucket,) + tuple(s[1:]),
                                    np.float32)
                        for k, s in shapes.items()
                        if k in batch_inputs}
                    return predictor.forward(**zeros)

                def build():
                    return guard(fwd) if guard is not None else fwd()
                return compile_cache.warmup_submit(
                    'serve[%s:%s]@%d' % (entry.name, tag, bucket),
                    build)
            futs = [warm_bucket(b)
                    for b in self._pow2_buckets(max_batch)]
        if wait:
            for f in futs:
                try:
                    f.result(timeout=timeout)
                except Exception:
                    # a failed warm compile is a warm-start miss, not a
                    # serving failure: the hot path compiles lazily
                    pass
        return futs

    def _add_replica(self, entry, slot, predictor=None, warm=True):
        """Build + warm + attach one replica (caller holds the admin
        lock).  The worker attaches LAST, after the warm-up completed —
        the new replica's first flush rides compiled executables."""
        if predictor is None:
            predictor = self._build_predictor(slot=slot,
                                              **entry.build_kw)
        rep = _Replica(slot, predictor)
        if warm:
            self._warm_replica(entry, rep, wait=True)
        entry.replicas.append(rep)
        entry.batcher.add_worker(rep.rid, self._make_execute(rep))
        return rep

    # -- fleet scaling ------------------------------------------------------

    def scale_up(self, name, warm=True):
        """Add one replica on the next free disjoint device slot.
        Serializes with load/unload/reload on the per-model admin lock.
        Returns the new replica count; None when the model is
        unloaded/closing or no disjoint device set remains (the
        capacity refusals).  A GENUINE replica-build failure (missing
        checkpoint, stale builder source after a prebuilt reload)
        raises — the autoscaler logs it verbatim instead of
        misreporting it as a capacity limit."""
        entry = self._models.get(name)
        if entry is None:
            return None
        with entry.admin_lock:
            if entry.closed or entry.batcher is None:
                return None
            used = {r.rid for r in entry.replicas}
            slot = 0
            while slot in used or entry.batcher.slot_busy(slot):
                # slot_busy covers slots no live replica claims but a
                # quarantined worker (or a timed-out removal's zombie)
                # still occupies: attaching a replacement there would
                # collide with the wedged thread's devices and worker id
                slot += 1
            mesh = (entry.build_kw or {}).get('mesh')
            if mesh is not None:
                from ..parallel.mesh import submesh_capacity
                if slot >= submesh_capacity(mesh):
                    return None       # no disjoint device set left
            self._add_replica(entry, slot, warm=warm)
            instrument.inc('serving.scale_ups')
            self._note_replicas(entry)
            return len(entry.replicas)

    def scale_down(self, name):
        """Remove the newest replica, draining its in-flight flush at
        a flush boundary.  Never removes the last replica (unload does
        that).  Returns the new replica count, or None when nothing
        was removed."""
        entry = self._models.get(name)
        if entry is None:
            return None
        with entry.admin_lock:
            if entry.closed or len(entry.replicas) <= 1:
                return None
            sup = self._supervisor
            protected = sup.protected(name) if sup is not None else ()
            idx = None
            for i in range(len(entry.replicas) - 1, -1, -1):
                # never pick the replica currently being replaced: a
                # clear window right after a quarantine must not undo
                # the repair the fleet just paid for
                if entry.replicas[i].rid not in protected:
                    idx = i
                    break
            if idx is None:
                return None
            rep = entry.replicas.pop(idx)
            entry.batcher.remove_worker(rep.rid)
            # retire the removed replica's labeled series: a scraped
            # gauge/histogram for a replica that no longer exists would
            # report its last value forever, and a stale HistogramWindow
            # base for the name would clamp a later slot reuse to empty
            instrument.drop_labeled_metrics(model=name,
                                            replica=str(rep.rid))
            instrument.inc('serving.scale_downs')
            self._note_replicas(entry)
            return len(entry.replicas)

    def replica_count(self, name):
        return len(self._entry(name).replicas)

    def unload_model(self, name, drain=True, timeout=None):
        """Remove ``name``; ``drain=True`` serves what is already
        queued first, ``drain=False`` fails queued requests.  Holds the
        admin lock, so an in-flight autoscaler decision finishes first
        and later decisions see the model gone.

        The drain is BOUNDED by ``timeout`` (default
        ``MXTPU_SERVE_DRAIN_TIMEOUT``): a replica wedged mid-flush
        cannot hang the unload — past the deadline its residual
        requests fail with the typed
        :class:`~mxnet_tpu.serving.batcher.ReplicaQuarantinedError`."""
        with self._lock:
            entry = self._models.pop(name, None)
            sc = self._autoscaler
            sup = self._supervisor
        if entry is None:
            raise ModelNotFoundError('no model %r' % name)
        if sc is not None:
            sc.unwatch(name)
        if sup is not None:
            sup.unwatch(name)
        with entry.admin_lock:
            entry.closed = True
            entry.batcher.stop(drain=drain, timeout=timeout)
        # the model is gone: its WHOLE labeled series family (replica
        # gauge, per-replica/per-lane histograms and counters) must
        # leave the registry and the exposition — stale series would
        # scrape as live, and a server churning model names would grow
        # the registry without bound
        instrument.drop_labeled_metrics(model=name)
        self._note_models()

    def reload_model(self, name, prefix=None, epoch=None, symbol_json=None,
                     params=None, input_shapes=None, output_keys=None,
                     predictor=None, mesh=None, partition=None):
        """Hot-swap ``name``'s Predictors on EVERY replica.  All
        replacements are fully built BEFORE the first swap; a flush in
        progress finishes on the old executable (each swap takes the
        replica lock its execute hook holds), queued and future
        requests run the new one."""
        entry = self._entry(name)
        with entry.admin_lock:
            if entry.closed:
                raise ModelNotFoundError('model %r is unloading' % name)
            kw = dict(entry.build_kw or {})
            if input_shapes is None:
                input_shapes = kw.get('input_shapes') or \
                    entry.predictor._input_shapes
            # the SOURCE fields replace wholesale (epoch=None with a
            # prefix means "latest", not the stale pinned epoch);
            # non-source fields (output_keys, mesh/partition) inherit
            # the stored values unless explicitly re-passed — a partial
            # reload must not silently drop the output filter from the
            # fleet's build source
            kw.update(prefix=prefix, epoch=epoch, symbol_json=symbol_json,
                      params=params, input_shapes=input_shapes)
            if output_keys is not None:
                kw['output_keys'] = output_keys
            if mesh is not None:
                kw['mesh'] = mesh
            if partition is not None:
                kw['partition'] = partition
            if predictor is not None:
                new = list(predictor) if isinstance(
                    predictor, (list, tuple)) else [predictor]
                if len(new) != len(entry.replicas):
                    raise MXNetError(
                        'reload with prebuilt predictors needs one '
                        'per replica (%d), got %d'
                        % (len(entry.replicas), len(new)))
                # the builder SOURCE now describes the PREVIOUS
                # version: drop it so a later scale_up refuses loudly
                # instead of silently building a replica of the old
                # model next to the reloaded ones.  Non-source fields
                # survive — mesh in particular keeps the capacity math
                # (and the autoscaler's at-capacity shrink relief)
                # correct for a sharded fleet
                old = entry.build_kw or {}
                entry.build_kw = {'input_shapes': input_shapes,
                                  'output_keys': old.get('output_keys'),
                                  'mesh': old.get('mesh'),
                                  'partition': old.get('partition')}
            else:
                new = [self._build_predictor(slot=rep.rid, **kw)
                       for rep in entry.replicas]
                entry.build_kw = kw
            # warm every replacement BEFORE the first swap (same
            # contract as scale_up: a reload must not make the next
            # flush per bucket pay a cold compile on the request path;
            # traffic keeps flushing on the OLD predictors meanwhile)
            for rep, repl in zip(entry.replicas, new):
                self._warm_predictor(entry, repl,
                                     'reload-r%s' % rep.rid)
            for rep, repl in zip(entry.replicas, new):
                with rep.lock:
                    rep.predictor = repl
            entry.generation += 1
            entry.batcher.batch_inputs = set(new[0]._batch_inputs)
        instrument.inc('serving.reloads')
        return new[0]

    def models(self):
        with self._lock:
            return sorted(self._models)

    def _entry(self, name):
        with self._lock:
            entry = self._models.get(name)
        if entry is None:
            raise ModelNotFoundError('no model %r' % name)
        return entry

    # -- autoscaling --------------------------------------------------------

    def autoscale(self, name, slo_p99_ms=None, interval_s=None, **kw):
        """Enroll ``name`` with the closed-loop replica autoscaler
        (created + started on first use; one controller per server).
        ``slo_p99_ms`` defaults to ``MXTPU_SERVE_SLO_MS``,
        ``interval_s`` to ``MXTPU_SERVE_SCALE_INTERVAL``.  Returns the
        :class:`~mxnet_tpu.serving.autoscaler.ReplicaAutoscaler` so
        callers can read its decision log."""
        from .autoscaler import ReplicaAutoscaler
        self._entry(name)                      # typed error when absent
        if not instrument.metrics_enabled():
            # every control input (windowed e2e p99, shed counters) is
            # recorded through the metrics plane: without it the
            # controller would silently read empty windows forever
            raise MXNetError(
                'autoscale needs the metrics plane: set MXTPU_METRICS=1 '
                'or instrument.set_metrics(True) before enrolling')
        if slo_p99_ms is None:
            slo_p99_ms = float(config.get('MXTPU_SERVE_SLO_MS'))
        if slo_p99_ms <= 0:
            raise MXNetError('autoscale needs slo_p99_ms > 0 (or '
                             'MXTPU_SERVE_SLO_MS set)')
        with self._lock:
            if self._autoscaler is None:
                self._autoscaler = ReplicaAutoscaler(
                    self, interval_s=interval_s)
            sc = self._autoscaler
        if interval_s is not None:
            sc.interval_s = float(interval_s)
        sc.watch(name, slo_p99_ms=slo_p99_ms, **kw)
        return sc

    @property
    def autoscaler(self):
        return self._autoscaler

    # -- supervision --------------------------------------------------------

    def supervise(self, name, wedge_ms=None, interval_s=None, start=True):
        """Enroll ``name`` with the replica supervisor (created on
        first use; one per server): a replica wedged past ``wedge_ms``
        (default ``MXTPU_SERVE_WEDGE_MS``) or dead on an exception is
        quarantined, its in-flight requests replayed once at their
        lane's head, and a warmed replacement attached before the
        tear-down.  ``start=False`` (or ``interval_s <= 0``) skips the
        poll thread — drive ``supervisor.tick()`` manually.  Returns
        the :class:`~mxnet_tpu.serving.supervisor.FleetSupervisor` so
        callers can read its event log."""
        from .supervisor import FleetSupervisor
        self._entry(name)                      # typed error when absent
        with self._lock:
            if self._supervisor is None:
                self._supervisor = FleetSupervisor(
                    self, interval_s=interval_s)
            sup = self._supervisor
        if interval_s is not None:
            sup.interval_s = float(interval_s)
        sup.watch(name, wedge_ms=wedge_ms, start=start)
        return sup

    @property
    def supervisor(self):
        return self._supervisor

    # -- request path -------------------------------------------------------

    def submit(self, name, priority=None, deadline_ms=None, **inputs):
        """Enqueue one request; returns a Future resolving to the list
        of per-output numpy arrays (sliced to the request's rows).
        ``priority='interactive'`` rides the express lane (preempts
        batch coalescing at flush boundaries); default is the batch
        lane.  Raises :class:`ServerOverloadedError` when shedding.
        ``deadline_ms`` (default ``MXTPU_SERVE_DEADLINE_MS``; 0
        disables) bounds the wait: past it the request is dropped at
        coalesce time — never executed dead — and fails with
        :class:`DeadlineExceededError`."""
        return self._entry(name).batcher.submit(inputs,
                                                priority=priority,
                                                deadline_ms=deadline_ms)

    def predict(self, name, timeout=None, priority=None,
                deadline_ms=None, **inputs):
        """Blocking :meth:`submit` — the single-request client path."""
        if timeout is None:
            timeout = config.get('MXTPU_SERVE_REQUEST_TIMEOUT')
        return self.submit(name, priority=priority,
                           deadline_ms=deadline_ms,
                           **inputs).result(timeout=timeout)

    # -- maintenance --------------------------------------------------------

    def pause(self, name):
        self._entry(name).batcher.pause()

    def resume(self, name):
        self._entry(name).batcher.resume()

    def stats(self):
        """The serving slice of the metrics registry (counters/gauges/
        histograms whose name starts with ``serving.``)."""
        snap = instrument.metrics_snapshot()
        out = {}
        for kind in ('counters', 'gauges', 'histograms'):
            vals = {k: v for k, v in (snap.get(kind) or {}).items()
                    if k.startswith('serving.')}
            if vals:
                out[kind] = vals
        return out

    def close(self, drain=True, timeout=None):
        with self._lock:
            self._closed = True
            names = list(self._models)
            sc = self._autoscaler
            self._autoscaler = None
            sup = self._supervisor
            self._supervisor = None
        if sc is not None:
            sc.stop()
        if sup is not None:
            sup.stop()
        for name in names:
            try:
                self.unload_model(name, drain=drain, timeout=timeout)
            except ModelNotFoundError:
                pass

    def drain(self, timeout=None, reason='drain'):
        """Bounded graceful drain — the SIGTERM path.  Stops admission
        and the control threads (autoscaler, supervisor), flushes every
        model's lanes within ONE shared ``timeout`` budget (default
        ``MXTPU_SERVE_DRAIN_TIMEOUT``; residual in-flight requests on a
        wedged replica fail typed past it), then commits a final
        servewatch snapshot — stats, decision/supervision/postmortem
        rings — through the flight-recorder path.  Returns the
        snapshot."""
        from . import servewatch
        from .. import health
        if timeout is None:
            timeout = float(config.get('MXTPU_SERVE_DRAIN_TIMEOUT'))
        t0 = time.monotonic()
        t_end = t0 + max(0.0, float(timeout))
        with self._lock:
            names = list(self._models)
            sc = self._autoscaler
            sup = self._supervisor
        snap = {
            'reason': reason,
            'models': names,
            # stats snapshot BEFORE the unloads drop the per-model
            # labeled series
            'stats': self.stats(),
        }
        self.close(drain=True,
                   timeout=max(0.0, t_end - time.monotonic()))
        snap['drain_secs'] = time.monotonic() - t0
        # the rings survive close(): capture them AFTER so repairs and
        # postmortems committed during the drain itself are included
        snap['autoscaler_events'] = list(sc.events) if sc is not None \
            else []
        snap['supervisor_events'] = list(sup.events) if sup is not None \
            else []
        snap['servewatch'] = {
            'decisions': servewatch.decisions(),
            'supervision': servewatch.supervision_events(),
            'flushes': servewatch.flushes(),
            'postmortems': servewatch.postmortems(),
        }
        rec = health.flight_recorder()
        if rec is None:
            rec = health.install_flight_recorder()
        if rec is not None:
            rec.dump('serve-%s' % reason, extra=snap)
            snap['flight_path'] = rec.durable_path('serve-%s' % reason)
        else:
            # no recorder and no MXTPU_FLIGHT_RECORDER dir to install
            # one: the snapshot is still returned to the caller
            snap['flight_path'] = None
        instrument.inc('serving.drains')
        return snap

    def install_sigterm_drain(self, timeout=None):
        """Install a SIGTERM handler that runs :meth:`drain` (bounded)
        before chaining the previous handler — or re-raising with the
        default disposition, so the process still dies of SIGTERM after
        the drain (the same chain discipline as
        ``health.install_flight_recorder``).  Main-thread only (Python
        restricts ``signal.signal``); returns True when installed."""
        import os
        import signal
        if threading.current_thread() is not threading.main_thread():
            return False
        prev = signal.getsignal(signal.SIGTERM)

        def _on_term(signum, frame):
            try:
                self.drain(timeout=timeout, reason='sigterm')
            except Exception:      # noqa: BLE001 - still die of SIGTERM
                pass
            if callable(prev) and prev not in (signal.SIG_IGN,
                                               signal.SIG_DFL):
                prev(signum, frame)
            else:
                signal.signal(signum, signal.SIG_DFL)
                os.kill(os.getpid(), signum)

        signal.signal(signal.SIGTERM, _on_term)
        return True

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close(drain=False)
        return False
