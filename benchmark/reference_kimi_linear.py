"""The plain reference of the configuration ``kimi_linear_48b_a3b``: the Kimi
Linear language model's forward pass, loss and gradients in straightforward
float32 ``jax.numpy``, which ``correct`` in
``benchmark/drivers/fit_kimi_linear.py`` compares the program with.  The
benchmark's own copy of ``mxnet_tpu/models/kimi_linear_reference.py`` (the
same text below this docstring, held equal by ``tests/test_kimi_linear.py``):
a later change to the program cannot move it, and nothing in it is imported
from ``mxnet_tpu``.
"""
# -- everything below this line is the same in both copies ------------------
#
# The model (Moonshot AI Kimi Linear, ``model_type`` ``kimi_linear``,
# arXiv:2510.26692): blocks ``h = x + Op(RMSNorm(x))``, ``x' = h +
# FF(RMSNorm(h))``, a final RMS norm, an output head with a table of its own.
#
# ``Op`` is Kimi Delta Attention (``kda``): for a token t and a head,
#   q = l2norm(silu(conv(W_q x))) / sqrt(d),  k = l2norm(silu(conv(W_k x))),
#   v = silu(conv(W_v x))      (conv: causal, depthwise, zeros before the
#                               sequence's start)
#   g = -exp(A_log[head]) softplus(W_f2 W_f1 x + dt_bias), a channel
#   beta = sigmoid(W_b x), one a head
#   S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
#   o_t = S_t^T q_t,  output W_o [RMSNorm_d(o_t) sigmoid(W_g2 W_g1 x)]
# with S_0 = 0 at every sequence's start; or multi-head latent attention
# without a rotary embedding (``mla``): q = W_q x, [c, k_r] = W_kva x,
# [k_nope, v] = W_kvb RMSNorm(c) head by head, k = [k_nope, k_r] with k_r the
# same for every head, causal softmax attention at the scale 1 / sqrt of the
# key's size, W_o.  ``FF`` is a dense SwiGLU MLP in the leading layers and
# after them a layer of routed experts (sigmoid scores, a selection bias used
# for the choice only, weights normalised over the chosen experts and scaled)
# plus a shared expert that every token passes.
#
# Nothing here comes from ``mxnet_tpu``: no kernel, no sort, no chunk.  The
# delta rule is the recurrence above token by token (a ``lax.scan`` over t:
# no chunks, no WY form, no clamp), experts are a loop over the experts held
# with a mask, attention is a full masked softmax one head at a time, and
# what works token by token runs in blocks of tokens so that 16384 tokens
# over 20480 classes fit one chip.  For the gradients to fit it too, a block
# of tokens, a head and a layer are each a ``jax.checkpoint``, and the scan
# over t is nested, blocks of ``SCAN_BLOCK`` tokens each a checkpoint (the
# state of one sequence is heads x d x d x 4 B a token: kept at every token
# of 8192 it would be 34e9 B): the backward pass computes them again and
# keeps only their inputs, which changes no value.  Given ``experts_held``
# and a slice of the vocabulary it computes the same share as the program:
# what the absent experts would have added is left out, the shared expert is
# whole, and the partial result goes on.  With ``experts_held = (0,
# num_experts)`` it is the uncut model.
#
# ``config`` takes: layer_types (one entry a layer that is run, ``kda`` or
# ``mla``), first_k_dense_replace, kda_num_heads, num_attention_heads,
# kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim, num_experts,
# num_experts_per_token, experts_held (first, count), rms_norm_eps,
# moe_renormalize, routed_scaling_factor.  Other widths come from the
# weights' shapes.  ``params`` is keyed by the symbol's argument and
# auxiliary-state names (``param_names``).
import jax
import jax.numpy as jnp

TOKEN_BLOCK = 2048
SCAN_BLOCK = 128
TOPK_EPS = 1e-6     # ops/lm.py route's, the family's modelling code
L2_EPS = 1e-6


def layer_param_names(index, kind, dense):
    """Names of layer ``index``'s arrays, as ``models/kimi_linear.py`` names
    them."""
    p = 'l%d_' % index
    names = [p + 'op_norm_gamma', p + 'ff_norm_gamma', p + 'q_weight',
             p + 'o_weight']
    if kind == 'kda':
        names += [p + 'k_weight', p + 'v_weight', p + 'kda_q_conv_weight',
                  p + 'kda_k_conv_weight', p + 'kda_v_conv_weight',
                  p + 'f_a_weight', p + 'f_b_weight', p + 'kda_A_log',
                  p + 'kda_dt_bias', p + 'b_weight', p + 'g_a_weight',
                  p + 'g_b_weight', p + 'kda_o_norm_gamma']
    else:
        names += [p + 'kv_a_weight', p + 'kv_norm_gamma', p + 'kv_b_weight']
    if dense:
        names += [p + 'w1_weight', p + 'w3_weight', p + 'w2_weight']
    else:
        names += [p + 'router_weight', p + 'experts_w1_weight',
                  p + 'experts_w3_weight', p + 'experts_w2_weight',
                  p + 'shared_w1_weight', p + 'shared_w3_weight',
                  p + 'shared_w2_weight', p + 'moe_expert_bias']
    return names


def param_names(config):
    names = ['embed_weight', 'final_norm_gamma', 'lm_head_weight']
    for i, kind in enumerate(config['layer_types']):
        names += layer_param_names(i, kind,
                                   i < config['first_k_dense_replace'])
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


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def causal_conv(x, kernel):
    """The causal depthwise convolution of ``x`` (N, T, C) along T: ``c_t =
    sum_j kernel[:, j] x_{t-j}``, zeros before the sequence's start."""
    t = x.shape[1]
    out = jnp.zeros_like(x)
    for j in range(kernel.shape[1]):
        out = out + kernel[:, j] * jnp.pad(x, ((0, 0), (j, 0), (0, 0)))[:, :t]
    return out


def delta_rule(q, k, v, g, beta):
    """The gated delta rule token by token.  ``q``, ``k``, ``g`` (N, T, H,
    d_k), ``v`` (N, T, H, d_v), ``beta`` (N, T, H); returns ``o`` (N, T, H,
    d_v).  The state starts at zero for every sequence and head."""
    n, t, h, dk = q.shape

    def token(state, x):
        q, k, v, g, beta = x
        state = state * jnp.exp(g)[..., None]
        held = jnp.einsum('nhk,nhkv->nhv', k, state)
        state = state + jnp.einsum('nhk,nhv->nhkv', k,
                                   beta[..., None] * (v - held))
        return state, jnp.einsum('nhk,nhkv->nhv', q, state)

    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    state = jnp.zeros((n, h, dk, v.shape[-1]), q.dtype)
    if t > SCAN_BLOCK and t % SCAN_BLOCK == 0:
        xs = tuple(x.reshape((t // SCAN_BLOCK, SCAN_BLOCK) + x.shape[1:])
                   for x in xs)
        out = jax.lax.scan(jax.checkpoint(block), state, xs)[1]
        out = out.reshape((t,) + out.shape[2:])
    else:
        out = block(state, xs)[1]
    return jnp.moveaxis(out, 0, 1)


def kda_gates(z, p, heads):
    """The log-decay (N, T, H, d) and beta (N, T, H) of a layer's input."""
    n, t, _ = z.shape
    decay = (z @ p['f_a_weight'].T) @ p['f_b_weight'].T + p['kda_dt_bias']
    g = -jnp.exp(p['kda_A_log'])[:, None] * \
        jax.nn.softplus(decay).reshape(n, t, heads, -1)
    return g, jax.nn.sigmoid(z @ p['b_weight'].T)


def kda(z, p, config):
    n, t, _ = z.shape
    heads = config['kda_num_heads']

    def mixed(name):
        return silu(causal_conv(z @ p[name + '_weight'].T,
                                p['kda_%s_conv_weight' % name])) \
            .reshape(n, t, heads, -1)
    q, k, v = mixed('q'), mixed('k'), mixed('v')
    g, beta = kda_gates(z, p, heads)
    o = delta_rule(l2norm(q) * q.shape[-1] ** -0.5, l2norm(k), v, g, beta)
    gate = jax.nn.sigmoid((z @ p['g_a_weight'].T) @ p['g_b_weight'].T)
    o = rms_norm(o, p['kda_o_norm_gamma'], config['rms_norm_eps']) * \
        gate.reshape(o.shape)
    return o.reshape(n, t, -1) @ p['o_weight'].T


def causal_attention(q, k, v, scale):
    """(N, H, T, D) queries and keys and (N, H, T, Dv) values; full masked
    softmax of ``scale q k^T``, one head at a time."""
    n, h, t, d = q.shape
    mask = jnp.tril(jnp.ones((t, t), bool))

    def one(args):
        qh, kh, vh = args
        scores = jnp.where(mask, (qh @ kh.T) * scale, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ vh

    out = jax.lax.map(jax.checkpoint(one),
                      (q.reshape(n * h, t, d), k.reshape(n * h, t, d),
                       v.reshape(n * h, t, -1)))
    return out.reshape(n, h, t, -1)


def mla(z, p, config):
    n, t, _ = z.shape
    heads = config['num_attention_heads']
    latent, nope = config['kv_lora_rank'], config['qk_nope_head_dim']
    q = (z @ p['q_weight'].T).reshape(n, t, heads, -1)
    kv_a = z @ p['kv_a_weight'].T
    c, k_shared = kv_a[..., :latent], kv_a[..., latent:]
    kv = (rms_norm(c, p['kv_norm_gamma'], config['rms_norm_eps']) @
          p['kv_b_weight'].T).reshape(n, t, heads, -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_shared[:, :, None, :],
                          (n, t, heads, k_shared.shape[-1]))], axis=-1)
    q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, kv[..., nope:]))
    out = causal_attention(q, k, v, q.shape[-1] ** -0.5)
    return out.transpose(0, 2, 1, 3).reshape(n, t, -1) @ p['o_weight'].T


def swiglu_mlp(z, w1, w3, w2):
    return _blocked(lambda x: (silu(x @ w1.T) * (x @ w3.T)) @ w2.T, z)


def route(z, router, bias, config):
    """Chosen experts (T, k) and their weights (T, k)."""
    scores = jax.nn.sigmoid(z @ router.T)
    _, chosen = jax.lax.top_k(scores + bias, config['num_experts_per_token'])
    weights = jnp.take_along_axis(scores, chosen, axis=1)
    if config.get('moe_renormalize', True):
        weights = weights / (weights.sum(axis=-1, keepdims=True) + TOPK_EPS)
    return chosen, weights * config.get('routed_scaling_factor', 1.0)


def expert_layer(z, router, bias, w1, w3, w2, config):
    """The held experts' part of the routed layer for tokens ``z`` (T, H),
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


def feed_forward(z, p, dense, config):
    """``FF`` of tokens ``z`` (T, H) and the routed layer's load (None for a
    dense layer): the held experts' part plus the shared expert, once."""
    if dense:
        return swiglu_mlp(z, p['w1_weight'], p['w3_weight'],
                          p['w2_weight']), None
    y, load = expert_layer(z, p['router_weight'], p['moe_expert_bias'],
                           p['experts_w1_weight'], p['experts_w3_weight'],
                           p['experts_w2_weight'], config)
    return y + swiglu_mlp(z, p['shared_w1_weight'], p['shared_w3_weight'],
                          p['shared_w2_weight']), load


def layer(x, p, kind, dense, config):
    """One block.  ``p`` holds the layer's arrays by the part of their names
    after ``l<index>_``.  Returns ``x'`` and the routed layer's load."""
    eps = config['rms_norm_eps']
    n, t, _ = x.shape
    z = rms_norm(x, p['op_norm_gamma'], eps)
    if kind == 'kda':
        op = kda(z, p, config)
    elif kind == 'mla':
        op = mla(z, p, config)
    else:
        raise ValueError('unknown layer type %r' % kind)
    h = x + op
    z = rms_norm(h, p['ff_norm_gamma'], eps).reshape(n * t, -1)
    ff, load = feed_forward(z, p, dense, config)
    return h + ff.reshape(n, t, -1), load


def forward(params, tokens, config):
    """Log-probabilities (N * T, V) of the next token over the vocabulary's
    rows held here, and each routed layer's load (layer index -> (held,)
    assignments).  ``tokens`` is (N, T) whole numbers."""
    n, t = tokens.shape
    load = {}
    with jax.default_matmul_precision('highest'):
        params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        x = params['embed_weight'][jnp.asarray(tokens).astype(jnp.int32)]
        for i, kind in enumerate(config['layer_types']):
            dense = i < config['first_k_dense_replace']
            prefix = 'l%d_' % i
            mine = {k[len(prefix):]: params[k]
                    for k in layer_param_names(i, kind, dense)}
            x, load_i = jax.checkpoint(
                lambda x, p, kind=kind, dense=dense:
                layer(x, p, kind, dense, config))(x, mine)
            if not dense:
                load[i] = load_i
        z = rms_norm(x, params['final_norm_gamma'], config['rms_norm_eps']) \
            .reshape(n * t, -1)
        table = params['lm_head_weight']
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


def decayed(name):
    """Whether MXNet's optimizers add the weight decay to this array's
    gradient: not to ``*_A_log`` and ``*_dt_bias``."""
    return name.endswith(('_weight', '_gamma'))


def adam_step(params, grads, mean, var, step, config):
    """One update of MXNet's Adam as the configuration states it: the
    decay is added to the gradient (not decoupled; MXNet decays the arrays
    whose names end in ``_weight`` or ``_gamma``: ``decayed``), and the bias
    correction scales the learning rate.  Returns name -> (parameter, mean,
    variance)."""
    lr, wd = config['learning_rate'], config['wd']
    b1, b2, eps = config['beta1'], config['beta2'], config['epsilon']
    lr_t = lr * (1.0 - b2 ** step) ** 0.5 / (1.0 - b1 ** step)
    out = {}
    for name, w in params.items():
        g = grads[name] * config['rescale_grad'] + \
            (wd if decayed(name) else 0.0) * w
        m = b1 * mean[name] + (1.0 - b1) * g
        v = b2 * var[name] + (1.0 - b2) * g * g
        out[name] = (w - lr_t * m / (jnp.sqrt(v) + eps), m, v)
    return out
