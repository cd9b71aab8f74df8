"""The ``fit_nemotron_h`` driver: the configuration
``nemotron3_super_120b_a12b`` through ``Module.fit``.

The run is ``drivers/fit_lm.py``'s (one ``fit`` call of two epochs, epoch 0
the warm-up, the window epoch 1 from one drain in its first callback to the
sync after ``fit`` returns, no wait in any later callback, nothing compiled
inside; a step is ``per_chip_batch`` packed sequences of ``seq_len`` Zipf
token ids from a ring of seeded host batches; the selection bias of every
expert layer balanced in set-up by its published rule and the run ended
unless the held experts then receive their share), and what the two drivers
before it give unchanged is imported from them: ``RingIter``,
``make_batches``, the forward measures, ``check_balance``,
``reference_step``, ``expert_nodes`` (``fit_lm``); ``update_readings`` for
arrays MXNet does not decay and ``refine_scopes`` for an operator that holds
loops (``fit_kimi_linear``).  Its own, because those are LFM2's and Kimi
Linear's: the weights it draws (``make_weights``: Mamba-2's ``A_log``,
``dt_bias`` and ``D`` as published), the walk that balances the bias
(``balance_bias``: blocks of one sub-block each), the reference's
configuration, the pins (``flops_nemotron_h``), the limits, the traced slice,
and ``run``, which neither driver parts from its own module's functions.

``correct`` holds the timed program's first step to the plain reference
(``benchmark/reference_nemotron_h.py``: the state-space recurrence token by
token, float32 at the highest precision) under the same parameters and
batch, as ``fit_lm.py`` words it: every token's log-probabilities, the
assignments a layer counted on held experts, zero tokens dropped, every
array's gradient as Adam was given it, every array's update, the bias
untouched and every trained array moved after the window, the last loss
under the first.
"""
import glob
import importlib
import json
import os
import time

import numpy as np

from .. import flops_nemotron_h, harness, reference, trace_reduce, \
    trace_scopes
from ..harness import BenchmarkError, log
from . import fit_kimi_linear, fit_lm
from .fit_kimi_linear import refine_scopes, update_readings  # noqa: F401
from .fit_lm import (RingIter, check_balance, expert_nodes,  # noqa: F401
                     forward_readings, make_batches, reference_step)

# ``Mamba2Mixer`` runs its convolution, its gates and its output's norm and
# gate a segment at a time inside the outer scan of ``scan``, as
# ``KimiDeltaAttention`` does: ``refine_scopes`` reads which scopes nest
# from this table of its module
fit_kimi_linear.NESTED.setdefault(('Mamba2Mixer', 'scan'),
                                  ('conv', 'gates', 'out_gate'))

# ``fit_lm.LIMITS``' measures, each limit between two readings on the chip
# at the cell's size (my chip runs, PR 36; PERF.md section 6 has every run):
# what the bf16 program reads against the float32 reference, and what the
# reference reads against itself with float8_e4m3 products, the nearest
# precision under the configuration's bf16 (or, for a change of state, the 1
# that no change reads); beside them what the reference reads with bf16
# products, which is the rounding the configuration states and no fault
# (seed 4000000007):
#                            bf16 program   bf16 products   float8   limit
#   log_prob_error           0.0321         0.0257          0.158    0.075
#   token_error_median       0.0154         0.0104          0.154    0.05
#   row_agreement            0.99936        0.99959         0.98895  0.997
#   gradient_error_median    0.0222         0.0169          0.267    0.075
#   gradient_error_worst     0.2351         0.1887          0.993    0.5
#   update_error_worst       4e-6           -        (unchanged: 1)  0.01
#   held_assignments_apart   0.0027         0.0023          0.0378   0.02
# Each limit is near the geometric mean of the program's reading and the
# control's (for the agreement, of their distances from 1), so that a fresh
# seed has a factor of two to four of room on either side; the worst
# gradient is a router's in every run (it turns on the few tokens whose
# choice bf16 tipped), the control's the attention block's.  The control is
# refused by every limit but the update's.  Over four seeds the program read
# 0.0321 to 0.0337, 0.0154 to 0.0158, 0.99929 to 0.99936, 0.0198 to 0.0222,
# 0.203 to 0.256 and, the one that moves with the seed, 0.0027 to 0.0062
# assignments apart (35 of a layer's 5630 at most), which is why that limit
# stands three times over the largest reading and not at the mean of the
# first.  They are tighter than
# ``fit_kimi_linear``'s, under which this control's ``held_assignments_apart``
# and ``row_agreement`` would hold: 22 of 512 scores a token tip less often
# than 8 of 256, and the float8 reference is nearer here.
# ``tests/test_nemotron_h.py`` plants each wrong model (a rotary embedding,
# gated experts, one expert too few, the shared expert left out, no skip from
# x) through these measures on the CPU at a small size.
LIMITS = {
    # name: (the worst reading that still holds, 'most' or 'least')
    'log_prob_error': (0.075, 'most'),
    'token_error_median': (0.05, 'most'),
    'row_agreement': (0.997, 'least'),
    'gradient_error_median': (0.075, 'most'),
    'gradient_error_worst': (0.5, 'most'),
    'update_error_worst': (0.01, 'most'),
}
# Assignments on held experts, program against reference, a layer: with 22 of
# 512 scores chosen a token, bf16 tips a quarter to six tenths of a hundredth
# of a layer's 5632 (the table above), float8 products 3.8 hundredths; at a
# rehearsal's sizes, where a hundredth is one assignment, at most 8
HELD_ASSIGNMENTS_APART_MAX = 0.02
HELD_ASSIGNMENTS_APART_FLOOR = fit_lm.HELD_ASSIGNMENTS_APART_FLOOR


def broken(readings):
    """Names of the limits that ``readings`` do not hold, sorted."""
    return sorted(
        name for name, value in readings.items()
        if not harness.holds({'value': value,
                              LIMITS[name][1]: LIMITS[name][0]}))


def make_weights(symbol, input_shapes, seed, time_step=(0.001, 0.1, 1e-4)):
    """``(arg_params, aux_params)`` as name -> float32 device array: every
    ``*_weight`` normal with variance 1 / fan-in (the second axis, also of
    the experts' stacked matrices and of the convolution's taps), every
    ``*_gamma`` one; Mamba-2's ``A_log`` the logarithm of a rate drawn evenly
    from 1 to 16, its ``dt_bias`` the inverse softplus of a step drawn
    log-evenly between ``time_step``'s first two and held to its third at
    least, its ``D`` one and its convolution's bias zero, as published; the
    selection bias zero (``balance_bias`` sets it) and the counting states
    zero."""
    import jax
    import jax.numpy as jnp
    arg_shapes, _, aux_shapes = symbol.infer_shape(**input_shapes)
    args = {n: tuple(s) for n, s in zip(symbol.list_arguments(), arg_shapes)
            if n not in input_shapes}
    aux = {n: tuple(s) for n, s in
           zip(symbol.list_auxiliary_states(), aux_shapes)}
    low, high, floor = time_step

    def make(name, shape, key):
        if name.endswith(('_gamma', '_ssm_D')):
            return jnp.ones(shape, jnp.float32)
        if name.endswith('_weight'):
            return jax.random.normal(key, shape, jnp.float32) * \
                np.float32(1.0 / np.sqrt(shape[1]))
        if name.endswith('_A_log'):
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              1.0, 16.0))
        if name.endswith('_dt_bias'):
            step = jnp.maximum(jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, np.log(low), np.log(high))), floor)
            return step + jnp.log(-jnp.expm1(-step))
        if name.endswith(('_conv_bias', '_expert_bias', '_expert_load',
                          '_expert_count', '_ssm_count')):
            return jnp.zeros(shape, jnp.float32)
        raise ValueError('benchmark/drivers/fit_nemotron_h.py does not '
                         'know how to make %r' % name)

    @jax.jit
    def make_all(key):
        shapes = dict(args, **aux)
        names = sorted(shapes)
        keys = jax.random.split(key, len(names))
        return {n: make(n, shapes[n], k) for n, k in zip(names, keys)}

    made = make_all(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))
    return ({n: made[n] for n in args}, {n: made[n] for n in aux})


def balance_bias(reference_lm, params, ring, config, passes, step):
    """Each ``E`` block's selection bias as the published balancing rule
    leaves it on the ring's batches, and the load it then gives:
    ``fit_lm.balance_bias``'s rule, schedule and order (from zero, ``passes``
    updates ``b_i += u * sign(mean(c) - c_i)`` a block, one after each of
    the ring's batches in turn, block by block on the stored router
    products), over this model's blocks: the plain reference's, in float32
    at the default matmul precision; only forward passes.

    Returns ``(bias, load)``: name -> ``(n_routed_experts,)`` float32 on the
    device, and block index -> ``(len(ring), n_routed_experts)`` assignments
    each expert receives from each batch under the returned bias."""
    import jax
    import jax.numpy as jnp
    experts = int(config['n_routed_experts'])
    start, decay, floor = (np.float32(step[k])
                           for k in ('start', 'decay', 'floor'))

    def counted(logits, b):
        _, chosen = jax.lax.top_k(jax.nn.sigmoid(logits) + b,
                                  config['num_experts_per_tok'])
        return jnp.zeros(experts, jnp.float32).at[chosen.reshape(-1)].add(1)

    @jax.jit
    def balanced(logits, b):
        def one(b, i):
            c = counted(jax.lax.dynamic_index_in_dim(
                logits, i % logits.shape[0], keepdims=False), b)
            u = jnp.maximum(start * decay ** i.astype(jnp.float32), floor)
            return b + u * jnp.sign(jnp.mean(c) - c), None
        b, _ = jax.lax.scan(one, b, jnp.arange(passes, dtype=jnp.int32))
        return b, jax.lax.map(lambda rows: counted(rows, b), logits)

    @jax.jit
    def router_products(x, p):
        u = reference_lm.rms_norm(x, p['norm_gamma'], config['norm_eps'])
        return u.reshape(-1, u.shape[-1]) @ p['router_weight'].T

    block = jax.jit(lambda x, p, kind: reference_lm.layer(x, p, kind,
                                                          config)[0],
                    static_argnames='kind')
    # one activation a batch of the ring stays on the device between the
    # blocks; a block's output replaces its input
    xs = [params['embed_weight'][jnp.asarray(tokens, jnp.int32)]
          for tokens in ring]
    out, load = {}, {}
    for i, kind in enumerate(config['pattern']):
        prefix = 'l%d_' % i
        p = {k[len(prefix):]: params[k]
             for k in reference_lm.layer_param_names(i, kind) if k in params}
        if kind == 'E':
            name = fit_lm.bias_name(i)
            out[name], load[i] = balanced(
                jnp.stack([router_products(x, p) for x in xs]),
                jnp.zeros(experts, jnp.float32))
            p['moe_expert_bias'] = out[name]
        if i + 1 < len(config['pattern']):
            for at, x in enumerate(xs):
                xs[at] = block(x, p, kind)
    return out, load


def check_pinned(symbol, input_shapes, config, rehearsal):
    want = config.get('pinned')
    if want is None:
        if rehearsal:
            return
        raise BenchmarkError('configuration %r pins no model'
                             % config['name'])
    built = flops_nemotron_h.pinned(symbol, input_shapes)
    for key, value in built.items():
        if value != want[key]:
            raise BenchmarkError(
                'configuration %r pins %s, and the program builds another '
                'model: %s' % (config['name'], key,
                               harness._first_difference(want[key], value)))


def reference_config(config):
    """The reference's ``config`` from the builder's arguments: the heads
    and groups as held here."""
    kwargs = config['builder']['kwargs']
    ranks = int(kwargs['mixers_held'][1])
    out = {k: kwargs[k] for k in (
        'mamba_head_dim', 'ssm_state_size', 'n_routed_experts',
        'num_experts_per_tok', 'norm_topk_prob', 'routed_scaling_factor')}
    out['pattern'] = kwargs['hybrid_override_pattern']
    out['norm_eps'] = kwargs['layer_norm_epsilon']
    out['mamba_num_heads'] = kwargs['mamba_num_heads'] // ranks
    out['n_groups'] = kwargs['n_groups'] // ranks
    out['num_attention_heads'] = kwargs['num_attention_heads'] // ranks
    out['num_key_value_heads'] = max(1,
                                     kwargs['num_key_value_heads'] // ranks)
    out['experts_held'] = tuple(kwargs['experts_held'])
    # ``check_balance`` reads the layer's experts under LFM2's key
    out['num_experts'] = out['n_routed_experts']
    return out


def run(ctx):
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import engine, instrument

    config, cell = harness.sizes(ctx), ctx.cell
    if ctx.chips != 1:
        raise BenchmarkError('the fit_nemotron_h driver runs one chip')
    traced = bool(ctx.trace)
    sequences = int(config['per_chip_batch'])
    length = int(config['seq_len'])
    vocabulary = int(config['vocab_size'])
    tokens_a_step = sequences * length
    warmup = int(cell['warmup_steps'])
    symbol = harness.build_symbol(config)       # an unknown model ends here
    input_shapes = {'data': (sequences, length),
                    'softmax_label': (sequences, length)}
    check_pinned(symbol, input_shapes, config, ctx.rehearsal)
    reference_lm = importlib.import_module(
        'benchmark.' + os.path.basename(config['reference'])[:-len('.py')])

    host = make_batches(ctx.seed, int(cell['ring']), sequences, length,
                        vocabulary, float(cell['zipf_exponent']))
    arg_params, aux_params = make_weights(
        symbol, input_shapes, ctx.seed,
        tuple(config[k] for k in ('time_step_min', 'time_step_max',
                                  'time_step_floor')))
    log('%d sequences x %d tokens a step, a ring of %d host batches; %d '
        'parameter arrays' % (sequences, length, len(host),
                              len(arg_params)))

    # the selection bias, balanced on the ring by its published rule; the
    # reference's first step, the program's and the window all run under it
    ref_config = reference_config(config)
    started = time.perf_counter()
    bias, load = balance_bias(reference_lm, arg_params,
                              [data for data, _ in host], ref_config,
                              int(cell['balance_passes']),
                              cell['balance_step'])
    check_balance(load, ref_config, cell['held_share_band'])
    if set(bias) != {k for k in aux_params if k.endswith('_expert_bias')}:
        raise BenchmarkError('the reference\'s expert layers are not the '
                             'program\'s: %s' % sorted(bias))
    aux_params.update(bias)
    bias_made = {k: np.array(v) for k, v in bias.items()}
    log('the selection bias balanced over the ring, %d passes a layer: '
        '%.1f s' % (int(cell['balance_passes']),
                    time.perf_counter() - started))
    del load

    # the plain reference's first step: forward pass, loss and gradients.
    # What the comparison needs goes to the host; the chip keeps nothing
    everything = dict(arg_params, **bias)
    started = time.perf_counter()
    log_prob_reference, load_reference, loss_reference, gradients = \
        reference_step(reference_lm, everything, host[0][0], host[0][1],
                       ref_config)
    prob_reference = np.exp(np.asarray(log_prob_reference, np.float64))
    load_reference = {k: np.asarray(v) for k, v in load_reference.items()}
    loss_reference = float(loss_reference) / tokens_a_step
    gradients = {k: np.asarray(v) for k, v in gradients.items()}
    # copies: the first step's comparison reads them, and the window's end
    before = {k: np.array(v) for k, v in arg_params.items()}
    label_first = host[0][1].reshape(-1)
    log('the reference\'s first step (forward, loss, gradients): %.1f s'
        % (time.perf_counter() - started))
    del everything, log_prob_reference

    dtype = {'bfloat16': jnp.bfloat16, 'float32': None}[
        config['compute_dtype']]
    module = mx.mod.Module(symbol, compute_dtype=dtype)
    iterator = RingIter([mx.io.DataBatch([d], [l], pad=0) for d, l in host],
                        warmup, traced)
    tracer = harness.SliceTrace(ctx.cell_name, 1) if traced else None
    trace_steps = int(cell['trace_steps'])
    moe = expert_nodes(symbol)
    fit = config['fit']
    optimizer = dict(config['optimizer'])
    name = optimizer.pop('name')
    # ``Module`` divides the summed gradient by the batch's rows unless told
    # otherwise, and is not told: the reference's Adam is given the same
    adam = dict(optimizer, rescale_grad=1.0 / sequences)
    state = {}
    stamps = []

    def drain(param):
        """The device, and the metric with the counters that ride it."""
        engine.sync(module.get_outputs())
        param.eval_metric.get()

    def batch_end(param):
        if param.epoch == 0:
            if param.nbatch == 0:
                state['prob_first'] = module.get_outputs()[0].asnumpy()
                after, aux = module.get_params()
                state['count_first'] = {
                    layer: aux[name + '_expert_count'].asnumpy()
                    for name, layer in moe}
                state['update_first'] = update_readings(
                    reference_lm, adam, dict(before), gradients,
                    {k: v.asnumpy() for k, v in after.items()},
                    module.fused_optimizer_state())
            return
        if param.nbatch == 0:
            # the one drain before the window; nothing after it waits
            drain(param)
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
            drain(param)
            tracer.stop()
            state['t1'] = tracer.t1
            state['steps'] = len(stamps)
            state['snap1'] = instrument.metrics_snapshot()

    def traced_batch_end(param):
        with harness.span('bench.batch_end'):
            batch_end(param)

    callbacks = [traced_batch_end if traced else batch_end]
    if fit.get('speedometer_every'):
        callbacks.append(mx.callback.Speedometer(
            sequences, int(fit['speedometer_every'])))
    # the module takes these very buffers and its first step donates them:
    # the parameters are on the chip once
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
    steps = state.get('steps', len(stamps))
    if 't0' not in state or steps < 1:
        raise BenchmarkError('the window held no step')
    compiled_inside = ctx.compiles.programs() - state['compiles0']
    # the last step ran on the first step's batch (``RingIter``)
    loss_last = reference.cross_entropy(module.get_outputs()[0].asnumpy(),
                                        label_first)
    window = t1 - state['t0']
    log('window %.3f s, %d steps of %d sequences (%d tokens); epoch end and '
        'return %.3f s of it; programs compiled or fetched inside the '
        'window: %d' % (window, steps, sequences, tokens_a_step,
                        t_returned - iterator.stopped_at, compiled_inside))
    if len(stamps) > 2:
        gaps = np.diff(stamps) * 1e3
        log('callback to callback: median %.2f ms, 5%% %.2f, 95%% %.2f, '
            'longest %.2f (step %d of %d)' % (
                np.median(gaps), np.percentile(gaps, 5),
                np.percentile(gaps, 95), gaps.max(), int(gaps.argmax()) + 1,
                len(gaps)))

    # -- correct ----------------------------------------------------------
    loss_first = reference.cross_entropy(state['prob_first'], label_first)
    log('loss on the first batch: reference %.5f, first step %.5f, the '
        'window\'s last step %.5f' % (loss_reference, loss_first, loss_last))
    readings = forward_readings(state['prob_first'], prob_reference)
    update, leaves = state['update_first']
    readings.update(update)
    refused = broken(readings)
    for key in sorted(readings):
        log('first step against the reference, %s: %.6f (at %s %s)%s'
            % (key, readings[key], LIMITS[key][1], LIMITS[key][0],
               '  REFUSED' if key in refused else ''))
    for key, (gradient, moved) in sorted(
            leaves.items(), key=lambda kv: -kv[1][0])[:8]:
        log('  gradient_error %.4f, update_error %.2e: %s'
            % (gradient, moved, key))
    apart = 0.0
    for _, layer in moe:
        routed, held, dropped, _ = state['count_first'][layer]
        want = float(load_reference[layer].sum())
        apart = max(apart, abs(held - want) / max(
            want, HELD_ASSIGNMENTS_APART_FLOOR / HELD_ASSIGNMENTS_APART_MAX))
        log('layer %d, first step: %d assignments routed, %d on held '
            'experts (the reference: %d, %.2f%% of the layer\'s), %d tokens '
            'dropped' % (layer, routed, held, want,
                         100.0 * want / max(routed, 1), dropped))
    arg_last, aux_last = module.get_params()
    last = dict(arg_last, **aux_last)
    bias_moved = sorted(k for k, v in bias_made.items()
                        if not np.array_equal(last[k].asnumpy(), v))
    unmoved = sorted(k for k, v in before.items()
                     if np.array_equal(last[k].asnumpy(), v))
    log('after the window: the selection bias bit for bit what set-up made '
        'in %d of %d layers, %d of %d trained arrays moved%s'
        % (len(bias_made) - len(bias_moved), len(bias_made),
           len(before) - len(unmoved), len(before),
           '  REFUSED: ' + ', '.join(bias_moved + unmoved)
           if bias_moved or unmoved else ''))
    totals = np.sum([aux_last[name + '_expert_count'].asnumpy()
                     for name, _ in moe], axis=0) if moe else np.zeros(4)
    log('in all: %d assignments routed, %d on held experts (%.3f%%), %d '
        'tokens dropped; %d times a layer was sent more than its buffer holds'
        % (totals[0], totals[1], 100.0 * totals[1] / max(totals[0], 1),
           totals[2], totals[3]))
    scanned = np.sum([v.asnumpy() for k, v in aux_last.items()
                      if k.endswith('_ssm_count')], axis=0)
    if np.ndim(scanned):
        log('Mamba-2 in all: %d tokens in %d chunks' % tuple(scanned))
    # every number compared, beside its limit
    compared = {key: {'value': readings[key], LIMITS[key][1]: LIMITS[key][0]}
                for key in sorted(readings)}
    compared['held_assignments_apart'] = {
        'value': apart, 'most': HELD_ASSIGNMENTS_APART_MAX}
    compared['tokens_dropped'] = {'value': float(totals[2]), 'most': 0.0}
    compared['bias_moved'] = {'value': float(len(bias_moved)), 'most': 0.0}
    compared['arrays_unmoved'] = {'value': float(len(unmoved)), 'most': 0.0}
    compared['loss_last_over_first'] = {'value': loss_last / loss_first,
                                        'under': 1.0}
    correct = all(harness.holds(entry) for entry in compared.values())
    if compiled_inside:
        raise BenchmarkError('%d program(s) compiled inside the window'
                             % compiled_inside)
    result = {
        'correct': correct, 'attempted': steps, 'failed': 0,
        't0': state['t0'], 'compared': compared,
        'end_to_end': {'fit_samples_per_s': sequences * steps / window},
        'devices': jax.devices()[:1],
    }
    if traced:
        result['slice'] = traced_slice(ctx, module, symbol, input_shapes,
                                       state, tracer, steps, window,
                                       sequences, tokens_a_step)
    return result


def traced_slice(ctx, module, symbol, input_shapes, state, tracer, steps,
                 window, sequences, tokens_a_step):
    """What the per-layer metrics read: the two snapshots, the reduced
    trace, device time by scope, and the step's FLOPs from the assignments
    the program counted over the slice."""
    slice_ = {
        'snap0': state['snap0'], 'snap1': state['snap1'],
        'steps': float(steps), 'chips': 1.0, 'window_s': window,
        'trace': tracer.reduced(), 'device_kind': ctx.device['kind'],
    }
    held = harness._term('counter:moe.assignments_held', slice_)
    routed = harness._term('counter:moe.assignments', slice_)
    dropped = harness._term('counter:moe.tokens_dropped', slice_)
    dense, per_assignment, _ = flops_nemotron_h.forward_macs_per_token(
        symbol, input_shapes)
    if held is not None:
        held_a_step = held / steps
        ssm = {k: harness._term('counter:ssm.' + k, slice_) or 0
               for k in ('tokens', 'chunks')}
        log('over the slice: %d assignments on held experts a step (%.4f%% '
            'of those routed), %d tokens dropped; Mamba-2: %d tokens in %d '
            'chunks' % (held_a_step, 100.0 * held / max(routed or 0, 1),
                        dropped or 0, ssm['tokens'], ssm['chunks']))
        slice_['step_flops'] = float(flops_nemotron_h.train_step_flops(
            dense, per_assignment, tokens_a_step, held_a_step))
        slice_['lm'] = dict(
            flops_nemotron_h.kernel_shapes(symbol, input_shapes),
            sequences=sequences, assignments_held_per_step=held_a_step)
    texts = getattr(module, 'fused_step_hlo', dict)()
    paths = glob.glob(os.path.join(tracer.dir, '**', '*.xplane.pb'),
                      recursive=True)
    if texts and paths and slice_['trace'] is not None:
        pairs = [(n['op'], n['name'])
                 for n in json.loads(symbol.tojson())['nodes']
                 if n['op'] != 'null']
        # the step the slice ran is the module's one fused program
        text = max(texts.values(), key=len)
        profile = trace_reduce.load(paths[0])
        slice_['scopes'] = scopes = trace_scopes.reduce_scopes(
            profile, text, pairs, harness.SLICE_SPAN, chips=1)
        if scopes:
            loops = refine_scopes(scopes, profile, text, pairs)
            log('device time by operator, ms a step (%.1f%% of the busy '
                'time joined to the HLO text, %.1f%% under an operator; '
                '%.3f ms a step of loops\' own events taken out):'
                % (100 * scopes['joined_s'] / max(scopes['busy_s'], 1e-12),
                   100 * scopes['scoped_s'] / max(scopes['busy_s'], 1e-12),
                   1e3 * loops / steps))
            for group in ('by_part', 'by_operator', 'by_inner', 'by_node',
                          'recomputed_by_operator', 'recomputed_by_inner'):
                log('  %s: %s' % (group, ', '.join(
                    '%s %.3f' % (k, 1e3 * v / steps) for k, v in sorted(
                        scopes[group].items(), key=lambda kv: -kv[1])[:48])))
    return slice_
