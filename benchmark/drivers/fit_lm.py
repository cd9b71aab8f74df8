"""The ``fit_lm`` driver: a language-model configuration through
``Module.fit``.

The same run as ``drivers/fit.py`` makes (one ``fit`` call of two epochs as
a user's script makes it, epoch 0 the warm-up, the window epoch 1 from one
drain in its first callback to the sync after ``fit`` returns, no wait in
any later callback, nothing compiled inside), on token sequences: a step is
``per_chip_batch`` packed sequences of ``seq_len`` token ids with their
next-token labels, fed the MXNet way as float32, from a ring of seeded host
batches handed out in turn.  One sample is one sequence.

The seed draws every weight, the vocabulary's order and every token, and
nothing else: each expert layer's selection bias starts at zero and is
balanced in set-up, before the reference's first step, by the rule the
published model trains under (``use_expert_bias``: Wang et al. 2024,
arXiv:2408.15664, section 3: ``b_i += u * sign(mean(c) - c_i)`` after a
batch, ``c`` the assignments each expert received), over the ring's own
batches (``balance_bias``).  The run ends with no result unless the held
experts then receive their share of every layer's assignments over the ring
(``check_balance``): the step's time follows that share, and it is the
deployment's eighth, not the seed's lot.

``correct`` holds the timed program's first step to the configuration's
plain reference (``benchmark/reference_lfm2_moe.py``: forward pass, loss,
``jax.grad`` and ``adam_step`` in float32) under the same parameters and
batch: the log-probabilities of every token over the vocabulary's slice;
the assignments each expert layer counted on its held experts; every
parameter's gradient as the optimizer was given it (Adam's mean after one
update is a tenth of it); every parameter after the update against the
reference's Adam on that gradient; after the window the selection bias bit
for bit what set-up made and every trained array moved from what the seed
drew; the tokens dropped to zero; and the loss of the window's last step,
which is on the first step's batch again, to the first.

With ``--trace 1`` the slice is ``trace_steps`` steps of epoch 1 between
two drains of the device and of the metric (the program writes its
device-side counters at a metric drain), under the profiler; the step's
HLO text (``Module.fused_step_hlo``) turns op events into device time by
operator (``benchmark/trace_scopes.py``).
"""
import glob
import importlib
import json
import os
import time

import numpy as np

from .. import flops_lm, harness, reference, trace_reduce, trace_scopes
from ..harness import BenchmarkError, log
from .fit import SeededBatchIter

# The first step's softmax outputs against the float32 reference's under
# the same parameters and batch, every token and every class of the slice.
# Three measures: ``drivers/fit.py``'s two (``reference.log_prob_error``,
# the root mean square of the difference of the log-probabilities over the
# spread of the reference's; ``reference.row_agreement``, the correlation
# of what is each token's own) and ``token_error_median``, the median over
# the tokens of the same error taken token by token.  bf16 activations tip
# a near-tie of the router the other way for a few tokens in a hundred,
# and those tokens' outputs then differ as a wrong model's do: they carry
# the root mean square and leave the median alone, while a wrong model
# moves every token.
#
# The first step's backward pass and update, array by array (``leaf_error``:
# the norm of the difference over the norm of the reference's):
# ``gradient_error``, of the gradient the optimizer was given (decay added,
# ``rescale_grad`` applied; the program's is Adam's mean after one update
# over ``1 - beta1``) against the reference's ``jax.grad``; its median over
# the arrays and its worst array are both held, since a router's gradient
# turns on the few tokens whose choice bf16 tipped and reads several times
# the others'.  ``update_error``, of the parameter's change against the
# reference's ``adam_step`` from the program's own gradient, so that it is
# float32 against float32: an array the optimizer never moved reads 1.  A
# state left unchanged altogether reads 1 as its ``gradient_error``.
#
# Each limit lies between two readings (PERF.md section 6 has them all):
# what the program reads on the chip at the cell's size, and what the
# reference reads against itself there with float8_e4m3 products, the
# nearest precision under the configuration's bf16, or, for a change of
# state, the 1 that no change reads (my chip runs, PR 28; nine seeds):
#                            bf16 program        float8 control   limit
#   log_prob_error           0.035 to 0.041      0.182            0.5
#   token_error_median       0.0204 to 0.0210    0.170            0.075
#   row_agreement            0.9992 to 0.9994    0.983            0.9
#   gradient_error_median    0.049 to 0.058      0.326            0.2
#   gradient_error_worst     0.25 to 0.35        0.790            0.6
#   update_error_worst       1e-6                (unchanged: 1)   0.01
# (the worst gradient is a router's in every run: it turns on the few
# tokens whose choice bf16 tipped).
# The control is refused by the median, by both gradient limits, and not
# by the two limits ``drivers/fit.py`` brought, which are for what a median
# cannot see: a quarter of the tokens wrong (the error), the right answers
# in the wrong rows (the agreement).  ``tests/test_lfm2_moe.py`` plants the
# control and each wrong model (top-3 routing, no selection bias, the
# convolution shifted by one, a gradient over half the batch, an array the
# optimizer never moved, an array whose gradient never arrived, a state
# left unchanged) through these functions on the CPU at a small size, with
# its readings there.
LIMITS = {
    # name: (the worst reading that still holds, 'most' or 'least')
    'log_prob_error': (0.5, 'most'),
    'token_error_median': (0.075, 'most'),
    'row_agreement': (0.9, 'least'),
    'gradient_error_median': (0.2, 'most'),
    'gradient_error_worst': (0.6, 'most'),
    'update_error_worst': (0.01, 'most'),
}
# Assignments on held experts, program against reference, a layer: equal
# but for the near-ties above, so at most a hundredth apart; at a
# rehearsal's sizes, where a hundredth is one assignment, at most 8.
HELD_ASSIGNMENTS_APART_MAX = 0.01
HELD_ASSIGNMENTS_APART_FLOOR = 8


def token_error_median(prob, prob_reference):
    """The median over the rows of: root mean square over the classes of
    the difference of the log-probabilities, over the standard deviation of
    the reference's log-probabilities.  In float64 on the host."""
    got = np.log(np.maximum(np.asarray(prob, np.float64), 1e-30))
    want = np.log(np.maximum(np.asarray(prob_reference, np.float64), 1e-30))
    spread = want.std()
    got -= want
    return float(np.median(np.sqrt(np.mean(np.square(got), axis=1))) /
                 spread)


def forward_readings(prob, prob_reference):
    """The three measures of the first step's outputs."""
    return {
        'log_prob_error': reference.log_prob_error(prob, prob_reference),
        'token_error_median': token_error_median(prob, prob_reference),
        'row_agreement': reference.row_agreement(prob, prob_reference)}


def leaf_error(got, want):
    """``|got - want| / |want|`` of one array, in float64 on the host."""
    got = np.asarray(got, np.float64).ravel()
    want = np.asarray(want, np.float64).ravel()
    return float(np.linalg.norm(got - want) /
                 max(np.linalg.norm(want), 1e-300))


def update_readings(reference_lm, adam, before, gradients, after, state):
    """The measures of the first step's backward pass and update.

    ``before`` and ``after`` are the parameters round the update by name,
    ``gradients`` the reference's (of the summed loss, as ``loss_and_grads``
    gives them), ``state`` the program's Adam state by name after the update,
    ``adam`` the optimizer's numbers with the ``rescale_grad`` the program
    used.  Arrays are taken off ``before`` and ``gradients`` as they are
    read: at the real size they are 1.9e9 B each on the host.  Returns the
    three readings and each array's two errors."""
    import jax
    leaves = {}
    cpu = jax.devices('cpu')[0]
    for name in sorted(gradients):
        w = np.asarray(before.pop(name), np.float32)
        given = gradients.pop(name) * np.float32(adam['rescale_grad']) + \
            np.float32(adam['wd']) * w
        got = np.asarray(state[name][0], np.float32) / \
            np.float32(1.0 - adam['beta1'])
        moved = np.asarray(after[name], np.float32) - w
        # the reference's Adam, from nothing, on the program's gradient
        with jax.default_device(cpu):
            zero = np.zeros_like(w)
            want = reference_lm.adam_step(
                {name: w}, {name: got}, {name: zero}, {name: zero}, 1,
                dict(adam, rescale_grad=1.0, wd=0.0))[name][0]
        leaves[name] = (leaf_error(got, given),
                        leaf_error(moved, np.asarray(want) - w))
    gradient = [g for g, _ in leaves.values()]
    return {'gradient_error_median': float(np.median(gradient)),
            'gradient_error_worst': float(np.max(gradient)),
            'update_error_worst': float(max(u for _, u in leaves.values()))
            }, leaves


def broken(readings):
    """Names of the limits that ``readings`` do not hold, sorted."""
    out = []
    for name, value in readings.items():
        limit, kind = LIMITS[name]
        holds = value <= limit if kind == 'most' else value >= limit
        if not (np.isfinite(value) and holds):
            out.append(name)
    return sorted(out)


class RingIter(SeededBatchIter):
    """``SeededBatchIter`` over a ring of host batches, handed out in
    turn.  After the warm-up epoch, the step after the limit or deadline
    is one more, on the ring's first batch: the window's last outputs are
    then of the batch the first step saw."""

    def __init__(self, batches, warmup_steps, traced):
        super(RingIter, self).__init__(batches[0], warmup_steps, traced)
        self._ring = batches
        self.handed_in_all = 0
        self._closes = False            # the warm-up epoch ends at its limit

    def reset(self):
        super(RingIter, self).reset()
        self._closes = True

    def _next(self):
        try:
            super(RingIter, self)._next()       # limit and deadline
        except StopIteration:
            if not self._closes:
                raise
            self._closes = False
            self.handed += 1
            return self._ring[0]
        batch = self._ring[self.handed_in_all % len(self._ring)]
        self.handed_in_all += 1
        return batch


def make_batches(seed, count, sequences, length, vocabulary, exponent):
    """``count`` host batches of ``sequences`` x ``length`` token ids,
    Zipf-distributed over a seeded order of the vocabulary, with the next
    token as label; float32, as MXNet feeds ids."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    weight = 1.0 / np.arange(1, vocabulary + 1) ** exponent
    ids = rng.permutation(vocabulary)
    drawn = ids[rng.choice(vocabulary, size=(count, sequences, length + 1),
                           p=weight / weight.sum())]
    return [(drawn[i, :, :-1].astype(np.float32),
             drawn[i, :, 1:].astype(np.float32)) for i in range(count)]


def make_weights(symbol, input_shapes, seed):
    """``(arg_params, aux_params)`` as name -> float32 device array: every
    ``*_weight`` normal with variance 1 / fan-in (the second axis, also of
    the experts' stacked matrices), every ``*_gamma`` one, the selection
    bias zero (``balance_bias`` sets it) and the counting states zero."""
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
        if name.endswith(('_expert_bias', '_expert_load', '_expert_count')):
            return jnp.zeros(shape, jnp.float32)
        raise ValueError('benchmark/drivers/fit_lm.py does not know how to '
                         'make %r' % name)

    @jax.jit
    def make_all(key):
        names = sorted(dict(args, **aux))
        keys = jax.random.split(key, len(names))
        shapes = dict(args, **aux)
        return {n: make(n, shapes[n], k) for n, k in zip(names, keys)}

    made = make_all(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))
    return ({n: made[n] for n in args}, {n: made[n] for n in aux})


def bias_name(layer):
    return 'l%d_moe_expert_bias' % layer


def balance_bias(reference_lm, params, ring, config, passes, step,
                 bias=None):
    """Each expert layer's selection bias as the published balancing rule
    leaves it on the ring's batches, and the load it then gives.

    The rule (Wang et al. 2024, arXiv:2408.15664, section 3): after a batch
    ``b_i += u * sign(mean(c) - c_i)``, ``c`` the assignments each of the
    layer's experts received in it; here from ``bias`` (name -> array; zero
    where None) over ``passes`` batches, the ring's in turn, with ``u``
    falling from ``step['start']`` by the factor ``step['decay']`` a pass
    down to ``step['floor']``.  The bias enters nothing but the choice of
    experts, so a layer's router sees the same input whatever its own bias
    is: the layers are balanced one after another, each on the input that
    the layers before it give under the bias they were left with, and a
    pass of one layer costs a ``top_k`` over the stored products with its
    router (``route``'s own choice: the largest of sigmoid plus bias), not
    a forward pass.  The model is the plain reference's, piece by piece
    (``layer``, ``rms_norm``, ``short_conv``, ``attention``,
    ``expert_layer``), in float32 at the default matmul precision; only
    forward passes, one a batch of the ring and what leads up to a router
    twice.

    Returns ``(bias, load)``: name -> ``(num_experts,)`` float32 on the
    device, and layer index -> ``(len(ring), num_experts)`` assignments each
    expert receives from each batch under the returned bias."""
    import jax
    import jax.numpy as jnp
    experts = int(config['num_experts'])
    eps = config['norm_eps']
    start, decay, floor = (np.float32(step[k])
                           for k in ('start', 'decay', 'floor'))

    def counted(logits, b):
        """``reference_lm.route``'s choice from the router's products, and
        how many assignments each expert then receives."""
        _, chosen = jax.lax.top_k(jax.nn.sigmoid(logits) + b,
                                  config['num_experts_per_tok'])
        return jnp.zeros(experts, jnp.float32).at[chosen.reshape(-1)].add(1)

    def to_router(x, p):
        """``reference_lm.layer`` as far as the feed-forward's input."""
        n, t, _ = x.shape
        z = reference_lm.rms_norm(x, p['op_norm_gamma'], eps)
        if 'conv_weight' in p:
            op = reference_lm.short_conv(z, p['conv_in_weight'],
                                         p['conv_weight'],
                                         p['conv_out_weight'])
        else:
            op = reference_lm.attention(
                z, p['q_weight'], p['k_weight'], p['v_weight'],
                p['o_weight'], p['q_norm_gamma'], p['k_norm_gamma'], config)
        h = x + op
        return h, reference_lm.rms_norm(h, p['ff_norm_gamma'],
                                        eps).reshape(n * t, -1)

    @jax.jit
    def router_products(x, p):
        return to_router(x, p)[1] @ p['router_weight'].T

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
    def past_experts(x, p, b):
        h, z = to_router(x, p)
        y, _ = reference_lm.expert_layer(
            z, p['router_weight'], b, p['experts_w1_weight'],
            p['experts_w3_weight'], p['experts_w2_weight'], config)
        return h + y.reshape(h.shape)

    dense_layer = jax.jit(
        lambda x, p, kind: reference_lm.layer(x, p, kind, True, config)[0],
        static_argnames='kind')
    # one activation a batch of the ring stays on the device between the
    # layers (134 MB each at 16384 tokens), and a layer's router products
    # (4 MB each); what leads up to the router is computed a second time
    # past the balanced layer rather than kept, so that set-up's peak
    # stays under the program's
    xs = [params['embed_weight'][jnp.asarray(tokens, jnp.int32)]
          for tokens in ring]
    out, load = {}, {}
    for i, kind in enumerate(config['layer_types']):
        dense = i < config['num_dense_layers']
        prefix = 'l%d_' % i
        p = {k[len(prefix):]: params[k]
             for k in reference_lm.layer_param_names(i, kind, dense)
             if k in params}
        if dense:
            for at, x in enumerate(xs):     # in place: the old one goes
                xs[at] = dense_layer(x, p, kind)
            continue
        b = (bias or {}).get(bias_name(i))
        b = jnp.zeros(experts, jnp.float32) if b is None else \
            jnp.asarray(b, jnp.float32)
        out[bias_name(i)], load[i] = balanced(
            jnp.stack([router_products(x, p) for x in xs]), b)
        if i + 1 < len(config['layer_types']):
            for at, x in enumerate(xs):
                xs[at] = past_experts(x, p, out[bias_name(i)])
    return out, load


def check_balance(load, config, band):
    """Ends the run unless, over the ring's batches together, the held
    experts of every expert layer receive their share of its assignments,
    ``held / num_experts``, within ``band`` (two factors of that share): a
    guard against a balance that did not happen, since the step's time
    follows the share.  Logs each layer's share and its fullest expert over
    the mean, which is held to nothing (where a layer sends every
    occurrence of the most frequent token one way, no bias divides them).
    Returns the shares by layer."""
    first, count = config['experts_held']
    even = float(count) / config['num_experts']
    shares = {}
    for layer, by_batch in sorted(load.items()):
        by_batch = np.asarray(by_batch, np.float64)
        whole = by_batch.sum(axis=0)
        held = by_batch[:, first:first + count].sum(axis=1) / \
            by_batch.sum(axis=1)
        shares[layer] = float(whole[first:first + count].sum() / whole.sum())
        log('layer %d, the bias balanced: held experts receive %.2f%% of the '
            'ring\'s assignments (single batches %.2f%% to %.2f%%; even is '
            '%.2f%%), the fullest of all %d experts %.3f times the mean, of '
            'the held %.3f' % (
                layer, 100 * shares[layer], 100 * held.min(),
                100 * held.max(), 100 * even, len(whole),
                whole.max() / whole.mean(),
                whole[first:first + count].max() / whole.mean()))
    outside = {layer: share for layer, share in shares.items()
               if not band[0] * even <= share <= band[1] * even}
    if outside:
        raise BenchmarkError(
            'the selection bias is not balanced: the held experts\' share '
            'of the ring\'s assignments is outside %.2f%% to %.2f%% in %s'
            % (100 * band[0] * even, 100 * band[1] * even, ', '.join(
                'layer %d (%.2f%%)' % (layer, 100 * share)
                for layer, share in sorted(outside.items()))))
    return shares


def reference_step(reference_lm, params, tokens, labels, config):
    """The plain reference's first step in one pass: the log-probabilities
    (N * T, V), each expert layer's load, the summed loss, and its gradient
    by every parameter but the selection bias (what ``loss_and_grads``
    differentiates, with the forward pass's other results kept)."""
    import jax
    import jax.numpy as jnp
    trained = {k: v for k, v in params.items()
               if not k.endswith('_expert_bias')}
    fixed = {k: v for k, v in params.items() if k.endswith('_expert_bias')}

    def loss(trained, fixed, tokens, labels):
        log_prob, load = reference_lm.forward(dict(trained, **fixed), tokens,
                                              config)
        picked = jnp.take_along_axis(log_prob, labels.reshape(-1, 1), axis=1)
        return -jnp.sum(picked), (log_prob, load)

    (total, (log_prob, load)), grads = jax.jit(jax.value_and_grad(
        loss, has_aux=True))(trained, fixed, jnp.asarray(tokens, jnp.int32),
                             jnp.asarray(labels, jnp.int32))
    return log_prob, load, total, grads


def check_pinned(symbol, input_shapes, config, rehearsal):
    want = config.get('pinned')
    if want is None:
        if rehearsal:
            return
        raise BenchmarkError('configuration %r pins no model'
                             % config['name'])
    built = flops_lm.pinned(symbol, input_shapes)
    for key, value in built.items():
        if value != want[key]:
            raise BenchmarkError(
                'configuration %r pins %s, and the program builds another '
                'model: %s' % (config['name'], key,
                               harness._first_difference(want[key], value)))


def reference_config(config):
    """The reference's ``config`` from the builder's arguments."""
    kwargs = config['builder']['kwargs']
    keys = ('hidden_size', 'layer_types', 'num_dense_layers',
            'num_attention_heads', 'num_key_value_heads', 'num_experts',
            'num_experts_per_tok', 'norm_eps', 'norm_topk_prob',
            'routed_scaling_factor', 'rope_theta')
    out = {k: kwargs[k] for k in keys}
    out['experts_held'] = tuple(kwargs['experts_held'])
    return out


def expert_nodes(symbol):
    """``(node name, layer index)`` of every ``SparseExperts`` node; the
    model names them ``l<index>_moe``."""
    nodes = json.loads(symbol.tojson())['nodes']
    return [(n['name'], int(n['name'][1:].split('_')[0]))
            for n in nodes if n['op'] == 'SparseExperts']


def run(ctx):
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import engine, instrument

    config, cell = harness.sizes(ctx), ctx.cell
    if ctx.chips != 1:
        raise BenchmarkError('the fit_lm driver runs one chip')
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
            leaves.items(), key=lambda kv: -kv[1][0])[:5]:
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
    dropped = harness._term('counter:moe.tokens_dropped', slice_)
    dense, per_assignment, _ = flops_lm.forward_macs_per_token(
        symbol, input_shapes)
    if held is not None:
        held_a_step = held / steps
        uneven = [harness._term(kind + ':moe.load_max_over_mean', slice_)
                  for kind in ('histogram_sum', 'histogram_count')]
        log('over the slice: %d assignments on held experts a step, %d '
            'tokens dropped; the fullest held expert over the mean, at the '
            'slice\'s drain: %s' % (
                held_a_step, dropped or 0,
                '%.3f' % (uneven[0] / uneven[1]) if uneven[1] else 'no '
                'reading'))
        slice_['step_flops'] = float(flops_lm.train_step_flops(
            dense, per_assignment, tokens_a_step, held_a_step))
        slice_['lm'] = dict(
            flops_lm.kernel_shapes(symbol, input_shapes),
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
        slice_['scopes'] = trace_scopes.reduce_scopes(
            trace_reduce.load(paths[0]), text, pairs, harness.SLICE_SPAN,
            chips=1)
        if slice_['scopes']:
            scopes = slice_['scopes']
            log('device time by operator, ms a step (%.1f%% of the busy '
                'time joined to the HLO text, %.1f%% under an operator):'
                % (100 * scopes['joined_s'] / max(scopes['busy_s'], 1e-12),
                   100 * scopes['scoped_s'] / max(scopes['busy_s'], 1e-12)))
            for group in ('by_part', 'by_operator', 'by_inner',
                          'recomputed_by_operator', 'recomputed_by_inner'):
                log('  %s: %s' % (group, ', '.join(
                    '%s %.3f' % (k, 1e3 * v / steps) for k, v in sorted(
                        scopes[group].items(), key=lambda kv: -kv[1]))))
            # what XLA named itself cannot say which pass it is: counted
            nodes = sum(1 for operator, _ in pairs
                        if operator == 'SparseExperts')
            for inner, count in scopes['kernel_instructions'].items():
                log('  %s: %d kernel instructions a step, %.1f an expert '
                    'layer (the model\'s forward and backward are 9; more '
                    'is a forward pass computed again)'
                    % (inner, count, count / max(nodes, 1)))
    return slice_
