"""The plain reference of ``models/lfm2_moe.py``: forward pass, loss and
(by ``jax.grad``) gradients of the LFM2-MoE language model in
straightforward float32 ``jax.numpy``.  The tests hold every new operator,
the whole model and one ``Module.fit`` step to it; ``benchmark/`` keeps a
copy of its own (``benchmark/reference_lfm2_moe.py``, the same text below
this docstring, held equal by ``tests/test_lfm2_moe.py``) so that a later
change to the program cannot move what ``correct`` compares with.
"""
# -- everything below this line is the same in both copies ------------------
#
# The model (LiquidAI LFM2-MoE, ``model_type`` ``lfm2_moe``): blocks
# ``h = x + Op(RMSNorm(x))``, ``x' = h + FF(RMSNorm(h))``.  ``Op`` is a gated
# short convolution or grouped-query attention with rotary embedding and an
# RMS norm on every head's query and key; ``FF`` is a dense SwiGLU MLP in
# the leading layers and a layer of routed experts after them (sigmoid
# scores, a selection bias used for the choice only, weights normalised
# over the chosen experts).  One table serves as embedding and output head.
#
# Nothing here comes from ``mxnet_tpu``: no kernel, no sort, no cache.
# Experts are a loop over the experts held with a mask, attention is a full
# masked softmax (one head at a time, so that 8192 x 8192 scores fit), and
# what works token by token runs in blocks of tokens so that 16384 tokens
# over 8192 classes fit one chip.  For the gradients to fit it too, a block
# of tokens, a head and a layer are each a ``jax.checkpoint``: the backward
# pass computes them again and keeps only their inputs, which changes no
# value.  Given ``experts_held`` and a slice of the
# vocabulary it computes the same share as the program: what the absent
# experts would have added is left out, and the partial result goes on.
# With ``experts_held = (0, num_experts)`` it is the uncut model.
#
# ``config`` takes the published names: hidden_size, layer_types (one entry
# a layer that is run), num_dense_layers, num_attention_heads,
# num_key_value_heads, num_experts, num_experts_per_tok, experts_held
# (first, count), norm_eps, norm_topk_prob, routed_scaling_factor,
# rope_theta.  Widths come from the weights' shapes.  ``params`` is keyed by
# the symbol's argument and auxiliary-state names (``param_names``).
import jax
import jax.numpy as jnp

TOKEN_BLOCK = 2048
TOPK_EPS = 1e-6     # the family's modelling code: sum of chosen scores + 1e-6


def layer_param_names(index, kind, dense):
    """Names of layer ``index``'s arrays, as ``models/lfm2_moe.py`` names
    them."""
    p = 'l%d_' % index
    names = [p + 'op_norm_gamma', p + 'ff_norm_gamma']
    if kind == 'conv':
        names += [p + 'conv_in_weight', p + 'conv_weight',
                  p + 'conv_out_weight']
    else:
        names += [p + 'q_weight', p + 'k_weight', p + 'v_weight',
                  p + 'o_weight', p + 'q_norm_gamma', p + 'k_norm_gamma']
    if dense:
        names += [p + 'w1_weight', p + 'w3_weight', p + 'w2_weight']
    else:
        names += [p + 'router_weight', p + 'experts_w1_weight',
                  p + 'experts_w3_weight', p + 'experts_w2_weight',
                  p + 'moe_expert_bias']
    return names


def param_names(config):
    names = ['embed_weight', 'final_norm_gamma']
    for i, kind in enumerate(config['layer_types']):
        names += layer_param_names(i, kind, i < config['num_dense_layers'])
    return names


def _blocked(fn, x):
    """``fn`` over the rows of ``x`` in blocks of ``TOKEN_BLOCK``."""
    rows = x.shape[0]
    if rows <= TOKEN_BLOCK or rows % TOKEN_BLOCK:
        return fn(x)
    out = jax.lax.map(jax.checkpoint(fn),
                      x.reshape((rows // TOKEN_BLOCK, TOKEN_BLOCK) +
                                x.shape[1:]))
    return jax.tree_util.tree_map(
        lambda o: o.reshape((rows,) + o.shape[2:]), out)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) +
                             eps) * weight


def silu(x):
    return x * jax.nn.sigmoid(x)


def rotary(x, theta):
    """Rotary embedding of ``x`` (..., T, D) at positions 0..T-1, the
    half-split pairing: element ``i`` turns with element ``i + D/2``."""
    t, d = x.shape[-2:]
    half = d // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def short_conv_mix(bcu, kernel):
    """The gated short convolution between its two projections:
    ``[B, C, u] = split3(bcu)``, ``g = B * u``, ``c_t = sum_j k_j g_{t-j}``
    per channel with zeros before the sequence's start, output ``C * c``.
    ``bcu`` is (N, T, 3 H), ``kernel`` (H, taps)."""
    b, c, u = jnp.split(bcu, 3, axis=-1)
    g = b * u
    t = g.shape[1]
    mixed = jnp.zeros_like(g)
    for j in range(kernel.shape[1]):
        shifted = jnp.pad(g, ((0, 0), (j, 0), (0, 0)))[:, :t]
        mixed = mixed + kernel[:, j] * shifted
    return c * mixed


def short_conv(z, w_in, kernel, w_out):
    return short_conv_mix(z @ w_in.T, kernel) @ w_out.T


def causal_attention(q, k, v):
    """(N, H, T, D) queries over (N, KV, T, D) keys and values, each
    key-value head serving H / KV query heads; full masked softmax of
    ``q k^T / sqrt(D)``, one head at a time."""
    n, h, t, d = q.shape
    group = h // k.shape[1]
    mask = jnp.tril(jnp.ones((t, t), bool))

    def one(args):
        qh, kh, vh = args
        scores = (qh @ kh.T) / jnp.sqrt(jnp.float32(d))
        scores = jnp.where(mask, scores, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ vh

    kk = jnp.repeat(k, group, axis=1).reshape(n * h, t, d)
    vv = jnp.repeat(v, group, axis=1).reshape(n * h, t, d)
    return jax.lax.map(jax.checkpoint(one),
                       (q.reshape(n * h, t, d), kk, vv)).reshape(n, h, t, d)


def attention(z, wq, wk, wv, wo, q_norm, k_norm, config):
    n, t, _ = z.shape
    heads = config['num_attention_heads']
    kv = config['num_key_value_heads']
    eps, theta = config['norm_eps'], float(config['rope_theta'])

    def split(x, count):
        return x.reshape(n, t, count, -1)

    q = rms_norm(split(z @ wq.T, heads), q_norm, eps)
    k = rms_norm(split(z @ wk.T, kv), k_norm, eps)
    v = split(z @ wv.T, kv)
    q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = causal_attention(rotary(q, theta), rotary(k, theta), v)
    return out.transpose(0, 2, 1, 3).reshape(n, t, -1) @ wo.T


def dense_mlp(z, w1, w3, w2):
    return _blocked(lambda x: (silu(x @ w1.T) * (x @ w3.T)) @ w2.T, z)


def route(z, router, bias, config):
    """Chosen experts (T, k) and their weights (T, k)."""
    scores = jax.nn.sigmoid(z @ router.T)
    _, chosen = jax.lax.top_k(scores + bias, config['num_experts_per_tok'])
    weights = jnp.take_along_axis(scores, chosen, axis=1)
    if config.get('norm_topk_prob', True):
        weights = weights / (weights.sum(axis=-1, keepdims=True) + TOPK_EPS)
    return chosen, weights * config.get('routed_scaling_factor', 1.0)


def expert_layer(z, router, bias, w1, w3, w2, config):
    """The held experts' part of the expert layer for tokens ``z`` (T, H),
    and how many assignments each held expert received.  ``w1`` and ``w3``
    are (held, H, F), ``w2`` (held, F, H)."""
    first, count = config['experts_held']

    def block(x):
        chosen, weights = route(x, router, bias, config)
        y = jnp.zeros_like(x)
        load = []
        for e in range(count):
            mine = chosen == first + e
            gate = jnp.sum(jnp.where(mine, weights, 0.0), axis=1)
            y = y + gate[:, None] * ((silu(x @ w1[e]) * (x @ w3[e])) @ w2[e])
            load.append(jnp.sum(mine, axis=1))
        return y, jnp.stack(load, axis=1)

    y, load = _blocked(block, z)
    return y, load.sum(axis=0)


def layer(x, p, kind, dense, config):
    """One block: ``h = x + Op(RMSNorm(x))``, ``x' = h + FF(RMSNorm(h))``.
    ``p`` holds the layer's arrays by the part of their names after
    ``l<index>_``.  Returns ``x'`` and the expert layer's load (None for a
    dense layer)."""
    eps = config['norm_eps']
    n, t, _ = x.shape
    z = rms_norm(x, p['op_norm_gamma'], eps)
    if kind == 'conv':
        op = short_conv(z, p['conv_in_weight'], p['conv_weight'],
                        p['conv_out_weight'])
    elif kind == 'full_attention':
        op = attention(z, p['q_weight'], p['k_weight'], p['v_weight'],
                       p['o_weight'], p['q_norm_gamma'], p['k_norm_gamma'],
                       config)
    else:
        raise ValueError('unknown layer type %r' % kind)
    h = x + op
    z = rms_norm(h, p['ff_norm_gamma'], eps).reshape(n * t, -1)
    if dense:
        ff, load = dense_mlp(z, p['w1_weight'], p['w3_weight'],
                             p['w2_weight']), None
    else:
        ff, load = expert_layer(
            z, p['router_weight'], p['moe_expert_bias'],
            p['experts_w1_weight'], p['experts_w3_weight'],
            p['experts_w2_weight'], config)
    return h + ff.reshape(n, t, -1), load


def forward(params, tokens, config):
    """Log-probabilities (N * T, V) of the next token over the vocabulary's
    rows held here, and each expert layer's load (layer index -> (held,)
    assignments).  ``tokens`` is (N, T) whole numbers."""
    n, t = tokens.shape
    load = {}
    with jax.default_matmul_precision('highest'):
        params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        x = params['embed_weight'][jnp.asarray(tokens).astype(jnp.int32)]
        for i, kind in enumerate(config['layer_types']):
            dense = i < config['num_dense_layers']
            prefix = 'l%d_' % i
            mine = {k[len(prefix):]: params[k]
                    for k in layer_param_names(i, kind, dense)}
            x, load_i = jax.checkpoint(
                lambda x, p, kind=kind, dense=dense:
                layer(x, p, kind, dense, config))(x, mine)
            if not dense:
                load[i] = load_i
        z = rms_norm(x, params['final_norm_gamma'], config['norm_eps']) \
            .reshape(n * t, -1)
        table = params['embed_weight']
        log_prob = _blocked(
            lambda rows: jax.nn.log_softmax(rows @ table.T, axis=-1), z)
    return log_prob, load


def loss(params, tokens, labels, config):
    """Sum over the tokens of the next token's negative log-likelihood:
    what ``SoftmaxOutput`` differentiates (its gradient is softmax minus
    one-hot, unnormalised; the optimizer's ``rescale_grad`` divides)."""
    log_prob, _ = forward(params, tokens, config)
    labels = jnp.asarray(labels).astype(jnp.int32).reshape(-1)
    return -jnp.sum(jnp.take_along_axis(log_prob, labels[:, None], axis=1))


def loss_and_grads(params, tokens, labels, config):
    """The loss and its gradient by every parameter but the selection
    bias, which gradient descent does not touch."""
    trained = {k: v for k, v in params.items()
               if not k.endswith('_expert_bias')}
    fixed = {k: v for k, v in params.items() if k.endswith('_expert_bias')}
    return jax.value_and_grad(
        lambda p: loss(dict(p, **fixed), tokens, labels, config))(trained)


def adam_step(params, grads, mean, var, step, config):
    """One update of MXNet's Adam as the configuration states it: the
    decay is added to the gradient (not decoupled; every trained array
    here ends in ``_weight`` or ``_gamma``, which MXNet decays), and the
    bias correction scales the learning rate.  Returns name -> (parameter,
    mean, variance)."""
    lr, wd = config['learning_rate'], config['wd']
    b1, b2, eps = config['beta1'], config['beta2'], config['epsilon']
    lr_t = lr * (1.0 - b2 ** step) ** 0.5 / (1.0 - b1 ** step)
    out = {}
    for name, w in params.items():
        g = grads[name] * config['rescale_grad'] + wd * w
        m = b1 * mean[name] + (1.0 - b1) * g
        v = b2 * var[name] + (1.0 - b2) * g * g
        out[name] = (w - lr_t * m / (jnp.sqrt(v) + eps), m, v)
    return out
