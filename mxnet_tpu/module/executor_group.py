"""DataParallelExecutorGroup — multi-device execution of one symbol.

The reference version (``python/mxnet/module/executor_group.py:69-225``)
creates one executor per GPU, slices each batch by ``decide_slices``
(``:199``) and reduces gradients through kvstore.  The TPU-native design
inverts this: **one** executor whose argument arrays are sharded over a
``jax.sharding.Mesh`` of the given contexts — data arrays split on the
batch axis, parameters replicated.  XLA's SPMD partitioner then emits the
per-device compute and the gradient all-reduce over ICI automatically; the
kvstore push/pull that the reference needed between executors disappears
into the compiled program (SURVEY.md §2.4 mapping).

``decide_slices`` and the merge/slice helpers are kept for API parity
(Monitor, bucketing and tests use them).
"""
from __future__ import annotations

import logging
from typing import Dict, List, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import ndarray as nd
from .. import perfwatch
from ..base import MXNetError
from ..context import Context
from ..executor import Executor
from ..ndarray import NDArray, ZerosWhenRead


def _split_input_slice(batch_size, work_load_list):
    """Slice boundaries per device (reference executor_manager.py:15)."""
    total_work_load = sum(work_load_list)
    batch_num_list = [round(work_load * batch_size / total_work_load)
                      for work_load in work_load_list]
    batch_num_sum = sum(batch_num_list)
    if batch_num_sum < batch_size:
        batch_num_list[-1] += batch_size - batch_num_sum
    slices = []
    end = 0
    for batch_num in batch_num_list:
        begin = int(min(end, batch_size))
        end = int(min(begin + batch_num, batch_size))
        if begin >= end:
            raise ValueError('Too many slices. Some splits are empty.')
        slices.append(slice(begin, end))
    return slices


class DataParallelExecutorGroup(object):
    """(reference executor_group.py:69)"""

    def __init__(self, symbol, contexts, workload, data_shapes, label_shapes,
                 param_names, for_training, inputs_need_grad,
                 shared_group=None, logger=logging, fixed_param_names=None,
                 grad_req='write', mesh_plan=None):
        # dp×tp product path (docs/parallel.md): an explicit
        # parallel.mesh.ShardingPlan overrides the legacy
        # one-axis-over-contexts mesh — batches place sharded over its
        # dp axis, parameters per its partition policy
        self.mesh_plan = mesh_plan
        self.param_names = param_names
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.symbol = symbol
        self.contexts = contexts
        self.workload = workload
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.logger = logger
        self.fixed_param_names = fixed_param_names or []
        self.grad_req_spec = grad_req
        self.shared_group = shared_group

        self.batch_size = None
        self.slices = None
        self.execs: List[Executor] = []
        self._mesh = None
        self._data_sharding = None
        self._replicated = None
        self.data_shapes = None
        self.label_shapes = None
        self.data_names = None
        self.label_names = None

        self.bind_exec(data_shapes, label_shapes, shared_group)

    # -- sharding ----------------------------------------------------------
    def _setup_mesh(self):
        if self.mesh_plan is not None:
            if len(self.contexts) > 1:
                raise MXNetError(
                    'Module(context=[...]) and fit(mesh=...) are '
                    'mutually exclusive device layouts — drop the '
                    'context list, the mesh covers the devices')
            self.mesh_plan.validate_batch(self.batch_size)
            self._mesh = self.mesh_plan.mesh
            self._data_sharding = self.mesh_plan.batch
            self._replicated = self.mesh_plan.replicated
        elif len(self.contexts) > 1:
            devices = np.array([c.jax_device for c in self.contexts])
            self._mesh = Mesh(devices, ('data',))
            self._data_sharding = NamedSharding(self._mesh, P('data'))
            self._replicated = NamedSharding(self._mesh, P())
        else:
            self._mesh = None
            self._data_sharding = None
            self._replicated = None

    def _place_data(self, value):
        if self._data_sharding is not None:
            placed = jax.device_put(value, self._data_sharding)
        else:
            placed = jax.device_put(value, self.contexts[0].jax_device)
        return perfwatch.ledger_alloc('io.h2d', placed)

    def _place_param(self, value, name=None):
        if self.mesh_plan is not None and name is not None and \
                name in self.param_names:
            return jax.device_put(
                value, self.mesh_plan.param_sharding(
                    name, np.shape(value),
                    dtype=getattr(value, 'dtype', None)))
        if self._replicated is not None:
            return jax.device_put(value, self._replicated)
        return jax.device_put(value, self.contexts[0].jax_device)

    # -- binding -----------------------------------------------------------
    def bind_exec(self, data_shapes, label_shapes, shared_group):
        self.data_shapes = [(n, tuple(s)) for n, s in data_shapes]
        self.label_shapes = [(n, tuple(s)) for n, s in label_shapes] \
            if label_shapes is not None else []
        self.data_names = [n for n, _ in self.data_shapes]
        self.label_names = [n for n, _ in self.label_shapes]
        self.batch_size = self.data_shapes[0][1][0]
        self.slices = _split_input_slice(self.batch_size, self.workload)
        self._setup_mesh()

        input_shapes = dict(self.data_shapes)
        input_shapes.update(dict(self.label_shapes))
        arg_shapes, _, aux_shapes = self.symbol.infer_shape(**input_shapes)
        if arg_shapes is None:
            raise MXNetError('shape inference failed for %s' % input_shapes)

        input_names = set(self.data_names + self.label_names)
        grad_req = {}
        for name in self.arg_names:
            if self.for_training:
                if name in self.param_names and \
                        name not in self.fixed_param_names:
                    grad_req[name] = self.grad_req_spec \
                        if isinstance(self.grad_req_spec, str) else \
                        self.grad_req_spec.get(name, 'write')
                elif name in self.data_names:
                    grad_req[name] = 'write' if self.inputs_need_grad \
                        else 'null'
                else:
                    grad_req[name] = 'null'
            else:
                grad_req[name] = 'null'

        shared_exec = shared_group.execs[0] if shared_group is not None \
            else None
        args, grads, aux = {}, {}, {}
        for name, shape in zip(self.arg_names, arg_shapes):
            is_input = name in input_names
            if shared_exec is not None and not is_input and \
                    name in shared_exec.arg_dict:
                # bucketing shares parameter storage with master executor
                args[name] = shared_exec.arg_dict[name]
                if name in shared_exec.grad_dict and \
                        grad_req.get(name, 'null') != 'null':
                    grads[name] = shared_exec.grad_dict[name]
                continue
            if is_input:
                placed = self._place_data(np.zeros(shape, np.float32))
            else:
                placed = self._place_param(np.zeros(shape, np.float32),
                                           name)
            args[name] = NDArray(placed, self.contexts[0])
            if grad_req.get(name, 'null') != 'null':
                # on the device only once read: the fused step never does
                grads[name] = ZerosWhenRead(
                    lambda shape=shape, name=name: self._place_param(
                        np.zeros(shape, np.float32), name),
                    self.contexts[0])
        for name, shape in zip(self.aux_names, aux_shapes):
            if shared_exec is not None and name in shared_exec.aux_dict:
                aux[name] = shared_exec.aux_dict[name]
            else:
                aux[name] = NDArray(self._place_param(
                    np.zeros(shape, np.float32)), self.contexts[0])

        executor = Executor(self.symbol, self.contexts[0], args,
                            grads or None, grad_req, aux)
        self.execs = [executor]

    def reshape(self, data_shapes, label_shapes):
        if data_shapes == self.data_shapes and \
                label_shapes == self.label_shapes:
            return
        self.bind_exec(data_shapes, label_shapes, self.shared_group)

    # -- params ------------------------------------------------------------
    def set_params(self, arg_params, aux_params):
        exec_ = self.execs[0]
        for name, arr in arg_params.items():
            if name in exec_.arg_dict:
                exec_.arg_dict[name]._set_data(
                    self._place_param(arr.handle if isinstance(arr, NDArray)
                                      else np.asarray(arr), name))
        for name, arr in (aux_params or {}).items():
            if name in exec_.aux_dict:
                exec_.aux_dict[name]._set_data(
                    self._place_param(arr.handle if isinstance(arr, NDArray)
                                      else np.asarray(arr)))

    def get_params(self, arg_params, aux_params):
        """Copy bound params out into the given dicts (executor_group.py:281)."""
        exec_ = self.execs[0]
        for name in self.param_names:
            if name in exec_.arg_dict:
                exec_.arg_dict[name].copyto(arg_params[name])
        for name in self.aux_names:
            if name in exec_.aux_dict:
                exec_.aux_dict[name].copyto(aux_params[name])

    # -- compute -----------------------------------------------------------
    def forward(self, data_batch, is_train=None):
        if is_train is None:
            is_train = self.for_training
        exec_ = self.execs[0]
        for (name, _), value in zip(self.data_shapes, data_batch.data):
            v = value.handle if isinstance(value, NDArray) else \
                np.asarray(value)
            exec_.arg_dict[name]._set_data(self._place_data(v))
        if self.label_shapes and data_batch.label:
            for (name, _), value in zip(self.label_shapes, data_batch.label):
                v = value.handle if isinstance(value, NDArray) else \
                    np.asarray(value)
                exec_.arg_dict[name]._set_data(self._place_data(v))
        exec_.forward(is_train=is_train)

    def backward(self, out_grads=None):
        assert self.for_training, 're-bind with for_training=True to run backward'
        self.execs[0].backward(out_grads)

    def forward_backward(self, data_batch, out_grads=None):
        """Fused fwd+bwd in one compiled program (Executor.forward_backward)."""
        exec_ = self.execs[0]
        for (name, _), value in zip(self.data_shapes, data_batch.data):
            v = value.handle if isinstance(value, NDArray) else \
                np.asarray(value)
            exec_.arg_dict[name]._set_data(self._place_data(v))
        if self.label_shapes and data_batch.label:
            for (name, _), value in zip(self.label_shapes, data_batch.label):
                v = value.handle if isinstance(value, NDArray) else \
                    np.asarray(value)
                exec_.arg_dict[name]._set_data(self._place_data(v))
        exec_.forward_backward(out_grads)

    def get_outputs(self, merge_multi_context=True):
        outs = self.execs[0].outputs
        if merge_multi_context:
            return outs
        return [[o] for o in outs]

    def get_input_grads(self, merge_multi_context=True):
        assert self.inputs_need_grad
        grads = [self.execs[0].grad_dict[n] for n in self.data_names]
        if merge_multi_context:
            return grads
        return [[g] for g in grads]

    def get_grads(self):
        """Gradient arrays for param_names (already globally reduced)."""
        return [self.execs[0].grad_dict[n] for n in self.param_names
                if n in self.execs[0].grad_dict]

    def update_metric(self, eval_metric, labels):
        # the numpy metric path fetches predictions to host — one
        # device sync per call (the counter the device-metric path is
        # measured against; metric.py module docstring)
        from .. import instrument
        instrument.inc('metric.host_syncs')
        eval_metric.update(labels, self.get_outputs())

    def install_monitor(self, mon):
        for exe in self.execs:
            mon.install(exe)
