"""The ``fit_kimi_linear`` driver: the configuration ``kimi_linear_48b_a3b``
through ``Module.fit``.

The run is ``drivers/fit_lm.py``'s (one ``fit`` call of two epochs, epoch 0
the warm-up, the window epoch 1 from one drain in its first callback to the
sync after ``fit`` returns, no wait in any later callback, nothing compiled
inside; a step is ``per_chip_batch`` packed sequences of ``seq_len`` Zipf
token ids from a ring of seeded host batches; the selection bias of every
expert layer balanced in set-up by its published rule and the run ended
unless the held experts then receive their share), and what that driver
gives unchanged is imported from it: ``RingIter``, ``make_batches``, the
forward measures, ``check_balance``, ``reference_step``, ``expert_nodes``.
Its own, because ``fit_lm.py``'s are LFM2's: the weights it draws
(``make_weights``: the decay's ``A_log`` and ``dt_bias`` as the published
implementation draws them), the walk that balances the bias
(``balance_bias``: Kimi Delta Attention, latent attention and a shared
expert on the way to each router), the reference's configuration, the
update's measures (MXNet's Adam decays no ``A_log`` and no ``dt_bias``), the
pins (``flops_kimi_linear``), the limits and the traced slice.

``correct`` holds the timed program's first step to the plain reference
(``benchmark/reference_kimi_linear.py``: the delta rule token by token,
float32 at the highest precision) under the same parameters and batch, as
``fit_lm.py`` words it: every token's log-probabilities, the assignments a
layer counted on held experts, zero tokens dropped, every array's gradient
as Adam was given it, every array's update, the bias untouched and every
trained array moved after the window, the last loss under the first.
"""
import glob
import importlib
import json
import os
import time

import numpy as np

from .. import flops_kimi_linear, harness, reference, trace_reduce, \
    trace_scopes
from ..harness import BenchmarkError, log
from . import fit_lm
from .fit_lm import (RingIter, check_balance, expert_nodes,  # noqa: F401
                     forward_readings, leaf_error, make_batches,
                     reference_step)

# ``fit_lm.LIMITS``' measures, each limit between two readings on the chip
# at the cell's size (my chip runs, PR 34; PERF.md section 6 has every run):
# what the bf16 program reads against the float32 reference, and what the
# reference reads against itself with float8_e4m3 products, the nearest
# precision under the configuration's bf16 (or, for a change of state, the 1
# that no change reads); beside them what the reference reads with bf16
# products, which is the rounding the configuration states and no fault:
#                            bf16 program   bf16 products   float8   limit
#   log_prob_error           0.040          0.032           0.346    0.15
#   token_error_median       0.024          0.016           0.368    0.1
#   row_agreement            0.9989         0.9993          0.949    0.98
#   gradient_error_median    0.027          0.021           0.495    0.12
#   gradient_error_worst     0.269          0.262           0.993    0.55
#   update_error_worst       under 1e-6     -        (unchanged: 1)   0.01
#   held_assignments_apart   0.0104         0.0091          0.134    0.04
# Each limit is near the geometric mean of the program's reading and the
# control's, so that a fresh seed has a factor of three to five of room on
# either side; the worst gradient is a router's in every run (it turns on
# the few tokens whose choice bf16 tipped), which is why its limit sits at
# twice the reading and not at its mean with the control's.  The control is
# refused by every limit but the update's.  ``tests/test_kimi_linear.py``
# plants the control and each wrong model (decay a head, no beta, no k k^T
# term, rotary on latent attention, one expert too few, the shared expert
# left out or counted twice) through these measures on the CPU at a small
# size.
LIMITS = {
    # name: (the worst reading that still holds, 'most' or 'least')
    'log_prob_error': (0.15, 'most'),
    'token_error_median': (0.1, 'most'),
    'row_agreement': (0.98, 'least'),
    'gradient_error_median': (0.12, 'most'),
    'gradient_error_worst': (0.55, 'most'),
    'update_error_worst': (0.01, 'most'),
}
# Assignments on held experts, program against reference, a layer: with 8 of
# 256 scores chosen a token, bf16 tips a hundredth of a layer's 4096 (the
# table above); at a rehearsal's sizes, where that is one assignment, at
# most 8
HELD_ASSIGNMENTS_APART_MAX = 0.04
HELD_ASSIGNMENTS_APART_FLOOR = fit_lm.HELD_ASSIGNMENTS_APART_FLOOR


def broken(readings):
    """Names of the limits that ``readings`` do not hold, sorted."""
    return sorted(
        name for name, value in readings.items()
        if not harness.holds({'value': value,
                              LIMITS[name][1]: LIMITS[name][0]}))


def update_readings(reference_lm, adam, before, gradients, after, state):
    """``fit_lm.update_readings`` for a model that holds arrays MXNet's
    optimizers do not decay (``reference_lm.decayed``): that function adds
    the decay to every array's reference gradient, so the undecayed arrays'
    are handed to it less what it will add."""
    scale = np.float32(adam['wd'] / adam['rescale_grad'])
    for name in gradients:
        if not reference_lm.decayed(name):
            gradients[name] = gradients[name] - \
                scale * np.asarray(before[name], np.float32)
    return fit_lm.update_readings(reference_lm, adam, before, gradients,
                                  after, state)


def make_weights(symbol, input_shapes, seed):
    """``(arg_params, aux_params)`` as name -> float32 device array: every
    ``*_weight`` normal with variance 1 / fan-in (the second axis, also of
    the experts' stacked matrices and of the convolutions' taps), every
    ``*_gamma`` one; Kimi Delta Attention's ``A_log`` the logarithm of a
    rate drawn evenly from 1 to 16 and its ``dt_bias`` the inverse softplus
    of a step drawn log-evenly from 0.001 to 0.1, as the published
    implementation draws both; the selection bias zero (``balance_bias``
    sets it) and the counting states zero."""
    import jax
    import jax.numpy as jnp
    arg_shapes, _, aux_shapes = symbol.infer_shape(**input_shapes)
    args = {n: tuple(s) for n, s in zip(symbol.list_arguments(), arg_shapes)
            if n not in input_shapes}
    aux = {n: tuple(s) for n, s in
           zip(symbol.list_auxiliary_states(), aux_shapes)}

    def make(name, shape, key):
        if name.endswith('_gamma'):
            return jnp.ones(shape, jnp.float32)
        if name.endswith('_weight'):
            return jax.random.normal(key, shape, jnp.float32) * \
                np.float32(1.0 / np.sqrt(shape[1]))
        if name.endswith('_A_log'):
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              1.0, 16.0))
        if name.endswith('_dt_bias'):
            step = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, np.log(0.001), np.log(0.1)))
            return step + jnp.log(-jnp.expm1(-step))
        if name.endswith(('_expert_bias', '_expert_load', '_expert_count',
                          '_kda_count')):
            return jnp.zeros(shape, jnp.float32)
        raise ValueError('benchmark/drivers/fit_kimi_linear.py does not '
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
    """Each expert layer's selection bias as the published balancing rule
    leaves it on the ring's batches, and the load it then gives:
    ``fit_lm.balance_bias``'s rule, schedule and order (from zero, ``passes``
    updates ``b_i += u * sign(mean(c) - c_i)`` a layer, one after each of
    the ring's batches in turn, layer by layer on the stored router
    products), over this model's layers: the plain reference's, piece by
    piece, in float32 at the default matmul precision; only forward passes.

    Returns ``(bias, load)``: name -> ``(num_experts,)`` float32 on the
    device, and layer index -> ``(len(ring), num_experts)`` assignments each
    expert receives from each batch under the returned bias."""
    import jax
    import jax.numpy as jnp
    experts = int(config['num_experts'])
    eps = config['rms_norm_eps']
    start, decay, floor = (np.float32(step[k])
                           for k in ('start', 'decay', 'floor'))

    def counted(logits, b):
        _, chosen = jax.lax.top_k(jax.nn.sigmoid(logits) + b,
                                  config['num_experts_per_token'])
        return jnp.zeros(experts, jnp.float32).at[chosen.reshape(-1)].add(1)

    def to_router(x, p, kind):
        """``reference_lm.layer`` as far as the feed-forward's input."""
        n, t, _ = x.shape
        z = reference_lm.rms_norm(x, p['op_norm_gamma'], eps)
        op = reference_lm.kda(z, p, config) if kind == 'kda' else \
            reference_lm.mla(z, p, config)
        h = x + op
        return h, reference_lm.rms_norm(h, p['ff_norm_gamma'],
                                        eps).reshape(n * t, -1)

    @jax.jit
    def balanced(logits, b):
        def one(b, i):
            c = counted(jax.lax.dynamic_index_in_dim(
                logits, i % logits.shape[0], keepdims=False), b)
            u = jnp.maximum(start * decay ** i.astype(jnp.float32), floor)
            return b + u * jnp.sign(jnp.mean(c) - c), None
        b, _ = jax.lax.scan(one, b, jnp.arange(passes, dtype=jnp.int32))
        return b, jax.lax.map(lambda rows: counted(rows, b), logits)

    def past_experts(x, p, b, kind):
        h, z = to_router(x, p, kind)
        y, _ = reference_lm.feed_forward(z, dict(p, moe_expert_bias=b),
                                         False, config)
        return h + y.reshape(h.shape)

    router_products = jax.jit(
        lambda x, p, kind: to_router(x, p, kind)[1] @ p['router_weight'].T,
        static_argnames='kind')
    past_experts = jax.jit(past_experts, static_argnames='kind')
    dense_layer = jax.jit(
        lambda x, p, kind: reference_lm.layer(x, p, kind, True, config)[0],
        static_argnames='kind')
    # one activation a batch of the ring stays on the device between the
    # layers; what leads up to a router is computed a second time past the
    # balanced layer rather than kept (``fit_lm.balance_bias``)
    xs = [params['embed_weight'][jnp.asarray(tokens, jnp.int32)]
          for tokens in ring]
    out, load = {}, {}
    for i, kind in enumerate(config['layer_types']):
        dense = i < config['first_k_dense_replace']
        prefix = 'l%d_' % i
        p = {k[len(prefix):]: params[k]
             for k in reference_lm.layer_param_names(i, kind, dense)
             if k in params}
        if dense:
            for at, x in enumerate(xs):     # in place: the old one goes
                xs[at] = dense_layer(x, p, kind)
            continue
        name = fit_lm.bias_name(i)
        out[name], load[i] = balanced(
            jnp.stack([router_products(x, p, kind) for x in xs]),
            jnp.zeros(experts, jnp.float32))
        if i + 1 < len(config['layer_types']):
            for at, x in enumerate(xs):
                xs[at] = past_experts(x, p, out[name], kind)
    return out, load


def check_pinned(symbol, input_shapes, config, rehearsal):
    want = config.get('pinned')
    if want is None:
        if rehearsal:
            return
        raise BenchmarkError('configuration %r pins no model'
                             % config['name'])
    built = flops_kimi_linear.pinned(symbol, input_shapes)
    for key, value in built.items():
        if value != want[key]:
            raise BenchmarkError(
                'configuration %r pins %s, and the program builds another '
                'model: %s' % (config['name'], key,
                               harness._first_difference(want[key], value)))


def reference_config(config):
    """The reference's ``config`` from the builder's arguments."""
    kwargs = config['builder']['kwargs']
    linear = kwargs['linear_attn_config']
    keys = ('first_k_dense_replace', 'num_attention_heads', 'kv_lora_rank',
            'qk_nope_head_dim', 'qk_rope_head_dim', 'v_head_dim',
            'num_experts', 'num_experts_per_token', 'rms_norm_eps',
            'moe_renormalize', 'routed_scaling_factor')
    out = {k: kwargs[k] for k in keys}
    out['layer_types'] = [
        'kda' if index + 1 in linear['kda_layers'] else 'mla'
        for index in range(kwargs['num_hidden_layers'])]
    out['kda_num_heads'] = linear['num_heads']
    out['experts_held'] = tuple(kwargs['experts_held'])
    return out


def run(ctx):
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import engine, instrument

    config, cell = harness.sizes(ctx), ctx.cell
    if ctx.chips != 1:
        raise BenchmarkError('the fit_kimi_linear driver runs one chip')
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
    arg_params, aux_params = make_weights(symbol, input_shapes, ctx.seed)
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
    log('in all: %d assignments routed, %d on held experts (%.2f%%), %d '
        'tokens dropped; %d times a layer was sent more than its buffer holds'
        % (totals[0], totals[1], 100.0 * totals[1] / max(totals[0], 1),
           totals[2], totals[3]))
    scanned = np.sum([v.asnumpy() for k, v in aux_last.items()
                      if k.endswith('_kda_count')], axis=0)
    if np.ndim(scanned):
        log('Kimi Delta Attention in all: %d tokens in %d chunks, %d '
            'log-decays (one a token and channel) under the floor the '
            'chunked form holds them to' % tuple(scanned))
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


# HLO opcodes whose event on the ``XLA Ops`` line lasts as long as the
# events of the computation they run, which are on the line too
LOOPS = ('while',)
# scopes an operator opens inside a loop that itself lies under one of its
# scopes: ``KimiDeltaAttention`` runs its convolutions, its gates and its
# output's norm and gate a segment at a time inside the outer scan of
# ``scan``, and ``trace_scopes`` names an instruction by the first scope
# after the node
NESTED = {('KimiDeltaAttention', 'scan'): ('conv', 'gates', 'out_gate')}


def _stated_name(step, name, depth=0):
    """The ``op_name`` an instruction states: its own, or its root's."""
    op_name = step.op_name.get(name)
    if op_name is None and name in step.calls and depth < 4:
        root = step.root.get(step.calls[name])
        return _stated_name(step, root, depth + 1) if root else None
    return op_name


def refine_scopes(scopes, profile, hlo_text, pairs, chips=1):
    """``trace_scopes.reduce_scopes``' result made right for an operator
    that holds loops.  The events of ``while`` instructions are taken out
    again: a loop's own event spans its body's, as a ``conditional``'s spans
    its branch's (which ``reduce_scopes`` leaves out), so a scan would
    count twice.  And an event under a scope of ``NESTED`` goes from the
    outer scope's entry of ``by_inner`` to its own.  Returns the seconds of
    loops' own events taken out."""
    window = trace_reduce.window_of(profile, harness.SLICE_SPAN)
    planes = trace_reduce.device_planes(profile)[:chips]
    if not scopes or not planes or window is None:
        return 0.0
    step = trace_scopes.StepScopes(hlo_text, pairs)
    out = 0.0
    for plane in planes:
        for start, end, text in trace_reduce.DeviceOps(plane, *window).sync:
            if ' conditional(' in text:
                continue
            seconds = (end - start) / 1e9 / len(planes)
            name = trace_scopes.event_instruction(text)
            if name not in step.operands:
                if trace_reduce.opcode(text) in LOOPS:
                    out += seconds
                    scopes['busy_s'] -= seconds
                continue
            scope = step.of(name)
            again = step.recomputed(name)
            inner = '%s/%s' % (scope.operator, scope.inner)
            groups = ['by_inner'] + ['recomputed_by_inner'] * again
            if trace_reduce.opcode(text) in LOOPS:
                out += seconds
                for key in ('busy_s', 'joined_s'):
                    scopes[key] -= seconds
                keys = [('by_part', scope.part)] if scope.part else []
                if scope.operator is not None:
                    scopes['scoped_s'] -= seconds
                    keys += [('by_operator', scope.operator),
                             ('by_node', '%s/%s' % (scope.operator,
                                                    scope.node))]
                    keys += [('recomputed_by_operator',
                              scope.operator)] * again
                    if scope.inner:
                        keys += [(group, inner) for group in groups]
                for group, key in keys:
                    if key in scopes[group]:
                        scopes[group][key] -= seconds
                continue
            words = [w for w in trace_scopes._SPLIT.split(
                _stated_name(step, name) or '') if w]
            nested = next(
                (w for w in words[words.index(scope.inner) + 1:]
                 if w in NESTED[(scope.operator, scope.inner)]), None) \
                if (scope.operator, scope.inner) in NESTED and \
                scope.inner in words else None
            if nested:
                for group in groups:
                    if inner in scopes[group]:
                        scopes[group][inner] -= seconds
                        to = '%s/%s' % (scope.operator, nested)
                        scopes[group][to] = scopes[group].get(to, 0.0) + \
                            seconds
    return out


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
    dropped = harness._term('counter:moe.tokens_dropped', slice_)
    dense, per_assignment, _ = flops_kimi_linear.forward_macs_per_token(
        symbol, input_shapes)
    if held is not None:
        held_a_step = held / steps
        kda = {k: harness._term('counter:kda.' + k, slice_) or 0
               for k in ('tokens', 'chunks', 'decays', 'decays_at_floor')}
        log('over the slice: %d assignments on held experts a step, %d '
            'tokens dropped; Kimi Delta Attention: %d tokens in %d chunks, '
            '%d of %d log-decays under the floor and held to it (%.4f%%)'
            % (held_a_step, dropped or 0, kda['tokens'], kda['chunks'],
               kda['decays_at_floor'], kda['decays'],
               100.0 * kda['decays_at_floor'] / max(kda['decays'], 1)))
        slice_['step_flops'] = float(flops_kimi_linear.train_step_flops(
            dense, per_assignment, tokens_a_step, held_a_step))
        slice_['lm'] = dict(
            flops_kimi_linear.kernel_shapes(symbol, input_shapes),
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
                        scopes[group].items(), key=lambda kv: -kv[1])[:40])))
    return slice_
