"""Standalone inference API (reference ``include/mxnet/c_predict_api.h`` /
``src/c_api/c_predict_api.cc:21-39``: MXPredCreate/SetInput/Forward/
GetOutput — the ABI used by amalgamation/mobile/JS builds).

``Predictor`` loads a ``prefix-symbol.json`` + params blob, prunes the
graph to the requested output, and serves jitted forward passes.

Every compiled forward (the base executor and every pow2-bucket
executor it reshapes out) runs through the step-compiler pass pipeline
(``fuse.apply_fuse_passes`` on the Executor's jit paths, ``MXTPU_FUSE``
knob): under ``aggressive`` the inference graph gets conv+BN weight
folding, BN->relu(->conv) kernel fusion, elementwise-epilogue collapse
and NHWC region growth before XLA sees it.

**Tensor-parallel serving** (``Predictor(mesh=..., partition=...)``,
docs/serving.md): models too big for one chip serve sharded.  The
symbol is compiled per pow2 bucket as an AOT executable with explicit
NamedSharding in/out shardings on a dp×tp mesh (the PR-8 product-path
rails): parameters placed per the partition policy (same
``ShardingPlan`` selection rule the sharded trainer uses, degradations
recorded per tensor for the sharding inspector), request batches split
over ``dp``, collectives emitted INSIDE the compiled program by XLA's
partitioner.  Executables key on the compile plane's
``(batch_sig, mesh_sig)`` signature (``compile_cache.sig_key``), and
:meth:`Predictor.warm_buckets` pre-compiles every bucket on the
compile-cache warmup pool — a warm sharded server takes ZERO hot-path
traces (``serving.sharded_aot_calls`` vs ``executor.xla_traces``).
"""
from __future__ import annotations

import numpy as np

from . import ndarray as nd
from . import symbol as sym_mod
from .base import MXNetError
from .context import Context, cpu, current_context
from .ndarray import NDArray


def _note_pad_waste(rows, bucket):
    """Pad-waste accounting for the pow2-bucketed forward paths: the
    rows between the real batch and the bucket it padded up to are
    compute spent on filler — ``serving.pad_waste_rows`` counts them
    and the per-bucket occupancy gauge says how full each compiled
    bucket runs (an always-half-empty bucket is a max_batch /
    coalescing tuning signal, see tools/explain_request.py)."""
    from . import instrument
    if not instrument.metrics_enabled() or not bucket:
        return
    if bucket > rows:
        instrument.inc('serving.pad_waste_rows', bucket - rows)
    instrument.set_gauge('serving.bucket_occupancy|bucket=%d' % bucket,
                         rows / float(bucket))


class Predictor(object):
    """(MXPredCreate / MXPredCreatePartialOut analogue)"""

    def __init__(self, symbol_json_str, param_raw_bytes_or_dict,
                 input_shapes, dev_type=None, dev_id=0,
                 output_keys=None, pad_to_bucket=False,
                 mesh=None, partition=None, devices=None):
        symbol = sym_mod.load_json(symbol_json_str) \
            if isinstance(symbol_json_str, str) else symbol_json_str
        if output_keys:
            internals = symbol.get_internals()
            outs = [internals[k if k.endswith('_output') else
                              k + '_output'] for k in output_keys]
            symbol = sym_mod.Group(outs)
        self._symbol = symbol
        # no dev_type: the default context, i.e. the accelerator where
        # one answers (context.current_context)
        self._ctx = Context(dev_type or current_context().device_type,
                            dev_id)
        self._plan = None

        if isinstance(param_raw_bytes_or_dict, (bytes, bytearray)):
            import io as _io
            import tempfile
            import os
            with tempfile.NamedTemporaryFile(delete=False) as f:
                f.write(param_raw_bytes_or_dict)
                path = f.name
            try:
                save_dict = nd.load(path)
            finally:
                os.unlink(path)
        else:
            save_dict = dict(param_raw_bytes_or_dict)
        arg_params, aux_params = {}, {}
        for k, v in save_dict.items():
            if k.startswith('arg:'):
                arg_params[k[4:]] = v
            elif k.startswith('aux:'):
                aux_params[k[4:]] = v
            else:
                arg_params[k] = v

        self._input_names = list(input_shapes.keys())
        self._input_shapes = {k: tuple(v) for k, v in input_shapes.items()}
        self._batch_inputs = self._infer_batch_inputs()
        self._out_arrays = None
        self._active_bucket = None
        self._valid_rows = None
        if mesh is not None:
            # tensor-parallel serving: no single-device Executor at all
            # — per-bucket AOT sharded executables (see _init_sharded)
            self._pad_to_bucket = True
            self._init_sharded(mesh, partition, devices, arg_params,
                               aux_params)
            return

        arg_shapes, out_shapes, aux_shapes = \
            symbol.infer_shape(**input_shapes)
        if arg_shapes is None:
            raise MXNetError('cannot infer shapes from %s' % input_shapes)
        args = {}
        for name, shape in zip(symbol.list_arguments(), arg_shapes):
            if name in input_shapes:
                args[name] = nd.zeros(shape, self._ctx)
            elif name in arg_params:
                args[name] = arg_params[name].as_in_context(self._ctx)
            elif name.endswith('label'):
                args[name] = nd.zeros(shape, self._ctx)
            else:
                raise MXNetError('missing parameter %s' % name)
        aux = {}
        for name, shape in zip(symbol.list_auxiliary_states(), aux_shapes):
            aux[name] = aux_params[name].as_in_context(self._ctx) \
                if name in aux_params else nd.zeros(shape, self._ctx)
        self._executor = symbol.bind(self._ctx, args, grad_req='null',
                                     aux_states=aux)
        # pow2 shape policy (compile_cache.pad_to_bucket): inputs whose
        # batch dim varies request-to-request are padded up to the next
        # power of two and served from a per-bucket executor (shared
        # parameter storage, own jit cache) — bounding the number of
        # distinct compiled inference shapes to O(log max_batch)
        # instead of one XLA program per request size.  Outputs are
        # sliced back to the real row count.  Row-coupled graphs
        # (cross-batch reductions) should keep the exact-shape path.
        self._pad_to_bucket = bool(pad_to_bucket)
        self._bucket_execs = {}

    def _infer_batch_inputs(self):
        """The named inputs that share the batch axis: leading dim equal
        to the declared batch size (the ``data`` input's when present,
        else the most common leading dim).  Only these are padded/
        reshaped by the pow2 bucket policy — per-model constants,
        lookup tables or scalar inputs ride along at their declared
        shapes instead of raising (the old one-batch-size-across-all-
        inputs restriction)."""
        leading = {k: s[0] for k, s in self._input_shapes.items() if s}
        if not leading:
            return set()
        if 'data' in leading:
            batch = leading['data']
        else:
            dims = sorted(leading.values())
            batch = max(dims, key=dims.count)
        return {k for k, d in leading.items() if d == batch}

    # -- tensor-parallel serving (mesh=...) ---------------------------------

    def _init_sharded(self, mesh, partition, devices, arg_params,
                      aux_params):
        """Build the sharded serving state: a dp×tp ShardingPlan over
        the given device set, parameters committed onto their partition
        shardings (degradations recorded per tensor — the PR-9
        sharding inspector surface), and an empty per-bucket AOT
        executable table keyed on ``(batch_sig, mesh_sig)``."""
        import threading

        import jax
        import jax.numpy as jnp

        from . import fuse
        from .parallel import mesh as pmesh
        plan = pmesh.ShardingPlan(
            pmesh.build_dp_tp_mesh(mesh, devices=devices),
            partition or 'auto')
        if plan.dp & (plan.dp - 1):
            raise MXNetError(
                'serving dp axis must be a power of two so pow2 request '
                'buckets stay dp-divisible, got dp=%d' % plan.dp)
        self._plan = plan
        # the pass pipeline runs once, like the Executor's one-program
        # jit paths — every bucket compiles the same rewritten graph
        self._prog_symbol = fuse.apply_fuse_passes(self._symbol, False)
        arg_shapes, _, aux_shapes = \
            self._symbol.infer_shape(**self._input_shapes)
        if arg_shapes is None:
            raise MXNetError('cannot infer shapes from %s'
                             % self._input_shapes)
        declared_batch = None
        if self._batch_inputs:
            declared_batch = self._input_shapes[
                sorted(self._batch_inputs)[0]][0]

        def as_jax(v):
            if isinstance(v, NDArray):
                return v.handle
            return jnp.asarray(v)

        params = {}
        self._batch_labels = {}     # label args that carry the batch axis
        for name, shape in zip(self._symbol.list_arguments(), arg_shapes):
            if name in self._input_shapes:
                continue
            if name in arg_params:
                v = as_jax(arg_params[name])
                sh = plan.param_sharding(name, shape, v.dtype)
                params[name] = jax.device_put(v, sh)
            elif name.endswith('label'):
                if shape and declared_batch is not None and \
                        shape[0] == declared_batch:
                    # batch-axis label: zeros rebuilt per bucket
                    self._batch_labels[name] = tuple(shape[1:])
                else:
                    params[name] = jax.device_put(
                        jnp.zeros(shape, jnp.float32), plan.replicated)
            else:
                raise MXNetError('missing parameter %s' % name)
        aux = {}
        for name, shape in zip(self._symbol.list_auxiliary_states(),
                               aux_shapes):
            v = as_jax(aux_params[name]) if name in aux_params \
                else jnp.zeros(shape, jnp.float32)
            # aux (BN moving stats) replicated: tiny, and eval-mode
            # reads must not depend on the partition policy
            aux[name] = jax.device_put(v, plan.replicated)
        self._params = params
        self._aux = aux
        plan.note_degraded()
        self._sharded_execs = {}
        self._exec_locks = {}
        self._exec_master = threading.Lock()

    def sharding_records(self):
        """The sharding-inspector document of the serving plan (what
        ``tools/explain_sharding.py`` renders) — per-tensor spec, shard
        bytes and DEGRADATION REASON when the requested tensor-parallel
        placement fell back to replicated.  None off the sharded path."""
        return None if self._plan is None else self._plan.records_doc()

    def _bucket_shapes(self, bucket):
        return {k: ((bucket,) + tuple(s[1:]) if k in self._batch_inputs
                    else s)
                for k, s in self._input_shapes.items()}

    def _sharded_sig(self, bucket):
        from . import compile_cache
        shapes = self._bucket_shapes(bucket)
        return compile_cache.sig_key(
            {k: (s, 'float32') for k, s in shapes.items()},
            mesh=self._plan.sig())

    def _bucket_entry(self, bucket):
        """The compiled AOT executable serving ``bucket`` — built on
        first use (or by :meth:`warm_buckets` on the warmup pool, in
        which case the hot path finds it already installed; a request
        racing an in-progress warm compile of ITS bucket blocks on that
        bucket's lock instead of tracing a duplicate)."""
        import threading

        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from . import compile_cache, instrument
        from .executor import _build_graph_fn
        from .parallel.mesh import DP_AXIS
        sig = self._sharded_sig(bucket)
        entry = self._sharded_execs.get(sig)
        if entry is not None:
            return entry
        with self._exec_master:
            lock = self._exec_locks.setdefault(bucket, threading.Lock())
        with lock:
            entry = self._sharded_execs.get(sig)
            if entry is not None:
                return entry
            plan = self._plan
            shapes = self._bucket_shapes(bucket)
            arg_shapes, out_shapes, _ = self._symbol.infer_shape(**shapes)
            graph_fn = _build_graph_fn(self._prog_symbol, False)

            def fwd(inputs, params, aux):
                merged = dict(params)
                merged.update(inputs)
                outs, _ = graph_fn(merged, aux,
                                   jax.random.PRNGKey(0))
                return outs

            wrapped = compile_cache.traced(
                'serve_sharded', self._prog_symbol, fwd,
                meta={'mesh': plan.sig()}, batch_argnum=0)
            in_shard = {}
            tmpl = {}
            for k, s in shapes.items():
                in_shard[k] = plan.batch if k in self._batch_inputs \
                    else plan.replicated
                tmpl[k] = jax.device_put(jnp.zeros(s, jnp.float32),
                                         in_shard[k])
            labels = {}
            for k, tail in self._batch_labels.items():
                in_shard[k] = plan.batch
                labels[k] = jax.device_put(
                    jnp.zeros((bucket,) + tail, jnp.float32), plan.batch)
            param_shard = {k: v.sharding for k, v in self._params.items()}
            aux_shard = {k: v.sharding for k, v in self._aux.items()}
            out_shard = [
                NamedSharding(plan.mesh, P(DP_AXIS))
                if s and int(s[0]) == bucket else plan.replicated
                for s in out_shapes]
            jitted = jax.jit(wrapped,
                             in_shardings=(in_shard, param_shard,
                                           aux_shard),
                             out_shardings=out_shard)
            inputs0 = dict(tmpl)
            inputs0.update(labels)
            compiled = jitted.lower(inputs0, self._params,
                                    self._aux).compile()
            try:
                from . import perfwatch
                if perfwatch.capture_on():
                    perfwatch.register_executable(
                        'serve_sharded', sig, compiled,
                        num_devices=plan.num_devices)
            except Exception:
                pass
            entry = {'exe': compiled, 'in_shard': in_shard,
                     'labels': labels, 'bucket': bucket}
            self._sharded_execs[sig] = entry
            instrument.inc('compile.shape_buckets')
            return entry

    def warm_buckets(self, max_batch):
        """Pre-compile the sharded executable of every pow2 bucket up
        to ``max_batch`` on the compile-cache warmup pool (traces land
        in ``compile.warmup_traces``, wall time in
        ``compile.warmup_secs``).  Returns the warmup Futures — wait on
        them and the serving hot path takes ZERO traces.  No-op list on
        the unsharded path (bucket executors there are built by
        ``forward`` per request size)."""
        from . import compile_cache
        if self._plan is None:
            return []
        futs = []
        top = compile_cache.pad_to_bucket(max(int(max_batch), 1),
                                          minimum=self._plan.dp)
        b = max(self._plan.dp, 1)
        while True:
            bucket = compile_cache.pad_to_bucket(b)
            futs.append(compile_cache.warmup_submit(
                'serve_sharded@%d' % bucket,
                lambda bucket=bucket: self._bucket_entry(bucket)))
            if bucket >= top:
                break
            b = bucket << 1
        return futs

    def _forward_sharded(self, kwargs):
        import jax

        from . import compile_cache, instrument
        rows = {np.asarray(v).shape[0] for k, v in kwargs.items()
                if k in self._batch_inputs}
        if len(rows) != 1:
            raise MXNetError('sharded forward needs one row count '
                             'across the batch-axis inputs %s, got %s'
                             % (sorted(self._batch_inputs), sorted(rows)))
        rows = rows.pop()
        bucket = compile_cache.pad_to_bucket(rows,
                                             minimum=self._plan.dp)
        entry = self._bucket_entry(bucket)
        inputs = {}
        for k, s in self._input_shapes.items():
            v = kwargs.get(k)
            if v is None:
                raise MXNetError('sharded forward needs every declared '
                                 'input; missing %r' % k)
            v = np.asarray(v, np.float32)
            if k in self._batch_inputs and v.shape[0] != bucket:
                v = np.concatenate(
                    [v, np.zeros((bucket - v.shape[0],) + v.shape[1:],
                                 v.dtype)], axis=0)
            inputs[k] = jax.device_put(v, entry['in_shard'][k])
        unknown = set(kwargs) - set(inputs)
        if unknown:
            raise MXNetError('unknown input(s) %s' % sorted(unknown))
        inputs.update(entry['labels'])
        outs = entry['exe'](inputs, self._params, self._aux)
        instrument.inc('serving.sharded_aot_calls')
        self._out_arrays = [NDArray(o) for o in outs]
        self._valid_rows = rows
        self._active_bucket = bucket
        _note_pad_waste(rows, bucket)
        return self._out_arrays

    def set_input(self, key, data):
        """(MXPredSetInput)"""
        if self._plan is not None:
            raise MXNetError('set_input is not available on the sharded '
                             '(mesh=) path: pass inputs to forward()')
        if key not in self._executor.arg_dict:
            raise MXNetError('unknown input %s' % key)
        self._executor.arg_dict[key][:] = np.asarray(data, np.float32)

    @property
    def num_outputs(self):
        return len(self._symbol.list_outputs())

    def forward(self, **kwargs):
        """(MXPredForward)"""
        if self._plan is not None:
            return self._forward_sharded(kwargs)
        if self._pad_to_bucket and kwargs:
            return self._forward_bucketed(kwargs)
        return self.forward_exact(**kwargs)

    def _bucket_executor(self, rows):
        """The executor bound at the pow2 bucket covering ``rows`` —
        created on first use by reshaping the base executor (parameters
        stay shared; only input/output arrays are fresh).  Only
        batch-axis inputs are rebatched; constant-shaped inputs keep
        their declared shapes."""
        from . import compile_cache, instrument
        bucket = compile_cache.pad_to_bucket(rows)
        exe = self._bucket_execs.get(bucket)
        if exe is None:
            shapes = {name: ((bucket,) + tuple(shape[1:])
                             if name in self._batch_inputs else shape)
                      for name, shape in self._input_shapes.items()}
            exe = self._executor.reshape(**shapes)
            self._bucket_execs[bucket] = exe
            # process-wide count of compiled shape buckets (a counter,
            # not a per-instance gauge: concurrent Predictors sum)
            instrument.inc('compile.shape_buckets')
        return exe, bucket

    def _forward_bucketed(self, kwargs):
        rows = {np.asarray(v).shape[0] for k, v in kwargs.items()
                if k in self._batch_inputs}
        if len(rows) > 1:
            raise MXNetError('pad_to_bucket needs one row count across '
                             'the batch-axis inputs %s, got %s'
                             % (sorted(self._batch_inputs), sorted(rows)))
        if not rows:
            # only constant-shaped inputs named: nothing to pad
            return self.forward_exact(**kwargs)
        rows = rows.pop()
        exe, bucket = self._bucket_executor(rows)
        for k, v in kwargs.items():
            if k not in exe.arg_dict:
                raise MXNetError('unknown input %s' % k)
            v = np.asarray(v, np.float32)
            if k in self._batch_inputs and v.shape[0] != bucket:
                v = np.concatenate(
                    [v, np.zeros((bucket - v.shape[0],) + v.shape[1:],
                                 v.dtype)], axis=0)
            exe.arg_dict[k][:] = v
        self._out_arrays = exe.forward(is_train=False)
        self._valid_rows = rows
        self._active_bucket = bucket
        _note_pad_waste(rows, bucket)
        return self._out_arrays

    def forward_exact(self, **kwargs):
        """Forward at the EXACT bound shapes, bypassing the pow2 bucket
        policy (row-coupled graphs; constant-input-only updates)."""
        if self._plan is not None:
            raise MXNetError('forward_exact is not available on the '
                             'sharded (mesh=) path: every sharded '
                             'forward rides a pow2-bucket AOT '
                             'executable')
        self._valid_rows = None
        self._active_bucket = None
        for k, v in kwargs.items():
            self.set_input(k, v)
        self._out_arrays = self._executor.forward(is_train=False)
        return self._out_arrays

    def get_output(self, index):
        """(MXPredGetOutput)"""
        if self._out_arrays is None:
            raise MXNetError('call forward first')
        out = self._out_arrays[index].asnumpy()
        if self._valid_rows is not None and out.ndim > 0 and \
                out.shape[0] == self._active_bucket:
            # padded rows are filler, not predictions
            out = out[:self._valid_rows]
        return out

    def reshape(self, input_shapes):
        """(MXPredReshape)"""
        if self._plan is not None:
            raise MXNetError('reshape is not available on the sharded '
                             '(mesh=) path: build a new Predictor (the '
                             'bucket table is shape-keyed already)')
        self._executor = self._executor.reshape(**input_shapes)
        self._input_shapes = {k: tuple(v) for k, v in input_shapes.items()}
        self._bucket_execs = {}
        self._out_arrays = None
        self._valid_rows = None
        self._active_bucket = None
        self._batch_inputs = self._infer_batch_inputs()


def load(prefix, epoch, input_shapes, dev_type='cpu', dev_id=0):
    """Build a Predictor from checkpoint files (the predict-api flow of
    loading prefix-symbol.json + prefix-XXXX.params)."""
    with open('%s-symbol.json' % prefix) as f:
        sym_json = f.read()
    params = nd.load('%s-%04d.params' % (prefix, epoch))
    return Predictor(sym_json, params, input_shapes, dev_type, dev_id)
