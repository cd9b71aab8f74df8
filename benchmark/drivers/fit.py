"""The ``fit`` driver: one configuration through ``Module.fit``.

One ``fit`` call of two epochs, as a user's script makes it (the
arguments of ``examples/train_imagenet.py``: SGD with momentum,
``kvstore='device'``, accuracy and cross-entropy, a ``Speedometer``).
The benchmark's iterator hands out one seeded batch again and again.
Epoch 0 is the warm-up: its few steps compile or fetch the step program
and its end runs everything an epoch's end runs.  In epoch 1 the
callback of the first step drains the device once and takes ``t0``;
from then on no callback waits for the device, so the step window runs
as it does for a user.  The iterator stops when ``--seconds`` have
passed; ``fit`` returns, the outputs are waited for, and ``t1`` is
taken.  The end of the epoch (metric drain, parameters fetched to the
host) is inside the window, once: its length is printed.

With ``--trace 1`` the slice is ``trace_steps`` steps of epoch 1 between
two drains, under the profiler.
"""
import time

import numpy as np

from .. import flops, harness, reference, weights
from ..harness import BenchmarkError, log

# The first step's softmax outputs against the float32 reference's under
# the same parameters and batch: every row and class, not one scalar.  An
# untrained network says nearly the same for every image (its largest
# probability is 0.008) and its loss sits within 2% of ln(1000) whatever
# it computes, so neither an absolute tolerance on probabilities nor one
# on the loss can fail.  ``reference.log_prob_error`` and
# ``reference.row_agreement`` can; measured on the CPU with the program's
# own bf16 step at batch 32, seed 2147484020 (PR 23; a scale for the
# tolerance, not a device number):
#                            error   agreement
#   bf16, ResNet-50          0.107   0.845
#   bf16, Inception-v3       0.220   0.674
#   float32, either          0.0003  1.000
#   every answer one row on  0.28    -0.04   (0.39, -0.03 for Inception-v3)
#   a uniform output         1.05    0
# The chip's readings are in PERF.md.  The bounds leave bf16 twice its
# CPU reading and refuse the last two; bf16's own error is too near a
# shifted row's for the first number alone to tell them apart.
LOG_PROB_ERROR_MAX = 0.5
ROW_AGREEMENT_MIN = 0.3


class SeededBatchIter(object):
    """Hands out one host batch until told to stop.
    ``next`` runs on the program's feed thread, one batch ahead of the
    step that consumes it."""

    def __init__(self, data_batch, warmup_steps, traced):
        data, label = data_batch.data[0], data_batch.label[0]
        self._batch = data_batch
        self.batch_size = data.shape[0]
        self.provide_data = [('data', tuple(data.shape))]
        self.provide_label = [('softmax_label', tuple(label.shape))]
        self._span = harness.span if traced else None
        self.handed = 0                 # in this epoch
        self.limit = warmup_steps       # batches this epoch may hand out
        self.deadline = None            # perf_counter after which to stop
        self.stopped_at = None

    def reset(self):
        # after the warm-up epoch only the callback's limit or deadline
        # ends an epoch
        self.handed = 0
        self.limit = None

    def __iter__(self):
        return self

    def _next(self):
        now = time.perf_counter()
        if (self.limit is not None and self.handed >= self.limit) or \
                (self.deadline is not None and now >= self.deadline):
            self.stopped_at = now
            raise StopIteration
        self.handed += 1
        return self._batch

    def __next__(self):
        if self._span is None:
            return self._next()
        with self._span('bench.iter_next'):
            return self._next()

    next = __next__


def run(ctx):
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import engine, instrument

    config, cell = harness.sizes(ctx), ctx.cell
    chips = ctx.chips
    traced = bool(ctx.trace)
    batch = int(config['per_chip_batch']) * chips
    shape = tuple(config['image_shape'])
    classes = int(config['num_classes'])
    warmup = int(cell['warmup_steps'])
    symbol = harness.build_symbol(config)
    harness.check_pinned(symbol, config, ctx.rehearsal)
    input_shapes = {'data': (batch,) + shape}

    rng = np.random.default_rng(np.random.SeedSequence([ctx.seed, 1]))
    data = rng.random((batch,) + shape, dtype=np.float32)
    label = rng.integers(0, classes, batch).astype(np.float32)
    arg_params, aux_params = weights.make(symbol, input_shapes, ctx.seed)
    log('batch %d x %s float32 on the host (%.0f MB); %d parameter arrays'
        % (batch, shape, data.nbytes / 1e6, len(arg_params)))

    # what the plain reference makes of the first batch
    devices = jax.devices()[:chips]
    arrays = dict(arg_params, **aux_params)
    where = devices[0]
    if chips > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        rows = Mesh(np.asarray(devices), ('rows',))
        arrays = jax.device_put(arrays, NamedSharding(rows, PartitionSpec()))
        where = NamedSharding(rows, PartitionSpec('rows'))
    started = time.perf_counter()
    arrays['data'] = jax.block_until_ready(jax.device_put(data, where))
    log('the batch copied to the chip%s once, alone: %.2f GB/s' % (
        's' if chips > 1 else '',
        data.nbytes / 1e9 / (time.perf_counter() - started)))
    prob_reference, _ = reference.forward_jit(symbol.tojson(), arrays, True)
    prob_reference = np.asarray(prob_reference)
    loss_reference = reference.cross_entropy(prob_reference, label)
    del arrays

    dtype = {'bfloat16': jnp.bfloat16, 'float32': None}[
        config['compute_dtype']]
    module = mx.mod.Module(symbol, compute_dtype=dtype)
    iterator = SeededBatchIter(mx.io.DataBatch([data], [label], pad=0),
                               warmup, traced)
    tracer = harness.SliceTrace(ctx.cell_name, chips) if traced else None
    trace_steps = int(cell['trace_steps'])
    state = {}
    stamps = []     # the host's clock at the callback of each window step

    def loss_now():
        return reference.cross_entropy(module.get_outputs()[0].asnumpy(),
                                       label)

    def batch_end(param):
        if param.epoch == 0:
            if param.nbatch == 0:
                state['prob_first'] = module.get_outputs()[0].asnumpy()
            return
        if param.nbatch == 0:
            # the one drain before the window; nothing after it waits
            engine.sync(module.get_outputs())
            state['compiles0'] = ctx.compiles.programs()
            if traced:
                iterator.limit = 1 + trace_steps
                state['snap0'] = instrument.metrics_snapshot()
                tracer.start()
                state['t0'] = tracer.t0
            else:
                iterator.limit = None
                state['t0'] = time.perf_counter()
                iterator.deadline = state['t0'] + ctx.seconds
            return
        stamps.append(time.perf_counter())
        if traced and len(stamps) == trace_steps:
            engine.sync(module.get_outputs())
            tracer.stop()
            state['t1'] = tracer.t1
            state['snap1'] = instrument.metrics_snapshot()

    def traced_batch_end(param):
        with harness.span('bench.batch_end'):
            batch_end(param)

    fit = config['fit']
    optimizer = dict(config['optimizer'])
    name = optimizer.pop('name')
    optimizer['rescale_grad'] = 1.0 / batch
    callbacks = [traced_batch_end if traced else batch_end]
    if fit.get('speedometer_every'):
        callbacks.append(mx.callback.Speedometer(
            batch, int(fit['speedometer_every'])))
    wrap = mx.nd.NDArray
    module.fit(iterator, num_epoch=2, optimizer=name,
               optimizer_params=optimizer, kvstore=fit['kvstore'],
               eval_metric=list(fit['eval_metric']),
               arg_params={k: wrap(v) for k, v in arg_params.items()},
               aux_params={k: wrap(v) for k, v in aux_params.items()},
               batch_end_callback=callbacks, mesh=cell.get('mesh'))
    t_returned = time.perf_counter()
    engine.sync(module.get_outputs())
    t1 = state.get('t1', time.perf_counter())
    steps = len(stamps)
    if 't0' not in state or steps < 1:
        raise BenchmarkError('the window held no step')
    compiled_inside = ctx.compiles.programs() - state['compiles0']
    loss_last = loss_now()
    window = t1 - state['t0']
    log('window %.3f s, %d steps of %d samples; epoch end and return '
        '%.3f s of it; programs compiled or fetched inside the window: %d'
        % (window, steps, batch, t_returned - iterator.stopped_at,
           compiled_inside))
    if len(stamps) > 2:
        gaps = np.diff(stamps) * 1e3
        log('callback to callback: median %.2f ms, 5%% %.2f, 95%% %.2f, '
            'longest %.2f (step %d of %d)' % (
                np.median(gaps), np.percentile(gaps, 5),
                np.percentile(gaps, 95), gaps.max(), int(gaps.argmax()) + 1,
                len(gaps)))
    loss_first = reference.cross_entropy(state['prob_first'], label)
    error = reference.log_prob_error(state['prob_first'], prob_reference)
    agreement = reference.row_agreement(state['prob_first'], prob_reference)
    log('loss: reference %.5f, first step %.5f, after the window %.5f'
        % (loss_reference, loss_first, loss_last))
    log('first step\'s log-probabilities against the reference\'s: error '
        '%.4f of their spread (at most %.2f), row agreement %.4f (at least '
        '%.2f)' % (error, LOG_PROB_ERROR_MAX, agreement, ROW_AGREEMENT_MIN))
    compared = {
        'log_prob_error': {'value': error, 'most': LOG_PROB_ERROR_MAX},
        'row_agreement': {'value': agreement, 'least': ROW_AGREEMENT_MIN},
        'loss_last_over_first': {'value': loss_last / loss_first,
                                 'under': 1.0}}
    correct = all(harness.holds(entry) for entry in compared.values())
    if compiled_inside:
        raise BenchmarkError('%d program(s) compiled inside the window'
                             % compiled_inside)
    result = {
        'correct': correct, 'attempted': steps, 'failed': 0,
        't0': state['t0'], 'compared': compared,
        'end_to_end': {'fit_samples_per_s': batch * steps / window},
        'devices': devices,
    }
    if traced:
        step_flops = flops.train_step_flops(symbol, input_shapes)
        result['slice'] = {
            'snap0': state['snap0'], 'snap1': state['snap1'],
            'steps': float(steps), 'chips': float(chips),
            'window_s': window, 'step_flops': float(step_flops),
            'trace': tracer.reduced(), 'device_kind': ctx.device['kind'],
        }
    return result
