"""The plain reference of ``models/nemotron_h.py``: forward pass, loss and
(by ``jax.grad``) gradients of the Nemotron-H language model in
straightforward float32 ``jax.numpy``.  The tests hold the new operator, the
whole model and one ``Module.fit`` step to it; ``benchmark/`` keeps a copy of
its own (``benchmark/reference_nemotron_h.py``, the same text below this
docstring, held equal by ``tests/test_nemotron_h.py``) so that a later change
to the program cannot move what ``correct`` compares with.
"""
# -- everything below this line is the same in both copies ------------------
#
# The model (NVIDIA Nemotron-H / Nemotron 3, ``model_type`` ``nemotron_h``):
# blocks of ONE sub-block each, ``x' = x + Mixer(RMSNorm(x))``, a final RMS
# norm, an output head with a table of its own.  ``Mixer`` is, by the
# block's letter in ``hybrid_override_pattern``:
#
# ``M``, Mamba-2 (Dao and Gu 2024, arXiv:2405.21060), H heads of P channels,
# G groups, a state of N a head, d = H P:
#   [z | xBC | dt] = W_in u                   (widths d, d + 2 G N, H)
#   xBC <- silu(conv(xBC) + b_conv)           (causal, depthwise, zeros before
#   [x | B | C] = xBC                          the sequence's start)
#   dt <- softplus(dt + dt_bias),  A = -exp(A_log), one a head
#   S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T   (S is P x N, S_0 = 0; head h
#   y_t = S_t C_t + D x_t                         reads group h // (H / G))
#   out = W_out [RMSNorm_group(y silu(z)) gamma], the mean square over each
#   group's d / G channels.
# ``*``, attention: q, k, v without bias, causal softmax at 1 / sqrt(head
# size), query head i on key-value head i // (heads / key-value heads), W_o;
# no rotary embedding.
# ``E``, LatentMoE: s = sigmoid(W_r u) over all the experts; the
# ``num_experts_per_tok`` largest of s + b (b the selection bias, used for
# the choice only); weights s_i / (sum of the chosen s + 1e-20) x
# ``routed_scaling_factor``; l = W_down u; expert i: W2_i relu(W1_i l)^2;
# out = W_up (sum_i w_i expert_i(l)) + V2 relu(V1 u)^2, the shared expert on
# the full hidden vector.
#
# Nothing here comes from ``mxnet_tpu``: no kernel, no sort, no chunk.  The
# state-space recurrence runs token by token (a ``lax.scan`` over t), experts
# are a loop over the experts held with a mask, attention is a full masked
# softmax one head at a time, and what works token by token runs in blocks of
# tokens so that 16384 tokens over 16384 classes fit one chip.  For the
# gradients to fit it too, a block of tokens, a head and a layer are each a
# ``jax.checkpoint``, and the scan over t is nested, blocks of ``SCAN_BLOCK``
# tokens each a checkpoint: the backward pass computes them again and keeps
# only their inputs, which changes no value.
#
# The share.  The weights say which heads are held: a mixer given ``W_in`` for
# 16 of 128 heads and one of 8 groups computes those heads' part of the sum
# that ``W_out`` makes, an attention given 4 of 32 query heads and the one
# key-value head they use computes theirs, and ``experts_held`` = (first,
# count) says which experts the stacked matrices are.  What the absent heads
# and experts would have added is left out, the shared expert and the
# latent's two projections are whole, and the partial result goes on.  With
# every head and ``experts_held = (0, n_routed_experts)`` it is the uncut
# model.
#
# ``config`` takes: pattern (the blocks that are run, one letter each),
# mamba_num_heads, mamba_head_dim, ssm_state_size, n_groups,
# num_attention_heads, num_key_value_heads (all as held here),
# n_routed_experts, num_experts_per_tok, experts_held (first, count),
# norm_eps, norm_topk_prob, routed_scaling_factor.  Other widths come from
# the weights' shapes.  ``params`` is keyed by the symbol's argument and
# auxiliary-state names (``param_names``).
import jax
import jax.numpy as jnp

TOKEN_BLOCK = 2048
SCAN_BLOCK = 128
TOPK_EPS = 1e-20    # the published modelling code's


def layer_param_names(index, kind):
    """Names of block ``index``'s arrays, as ``models/nemotron_h.py`` names
    them."""
    p = 'l%d_' % index
    names = [p + 'norm_gamma']
    if kind == 'M':
        names += [p + 'in_weight', p + 'ssm_conv_weight', p + 'ssm_conv_bias',
                  p + 'ssm_A_log', p + 'ssm_D', p + 'ssm_dt_bias',
                  p + 'ssm_norm_gamma', p + 'out_weight']
    elif kind == '*':
        names += [p + 'q_weight', p + 'k_weight', p + 'v_weight',
                  p + 'o_weight']
    elif kind == 'E':
        names += [p + 'router_weight', p + 'down_weight',
                  p + 'experts_w1_weight', p + 'experts_w2_weight',
                  p + 'up_weight', p + 'shared_w1_weight',
                  p + 'shared_w2_weight', p + 'moe_expert_bias']
    else:
        raise ValueError('unknown block %r' % kind)
    return names


def param_names(config):
    names = ['embed_weight', 'final_norm_gamma', 'lm_head_weight']
    for i, kind in enumerate(config['pattern']):
        names += layer_param_names(i, kind)
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


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def causal_conv(x, kernel, bias):
    """The causal depthwise convolution of ``x`` (N, T, C) along T: ``c_t =
    sum_j kernel[:, j] x_{t-j} + bias``, zeros before the sequence's
    start."""
    t = x.shape[1]
    out = jnp.zeros_like(x) + bias
    for j in range(kernel.shape[1]):
        out = out + kernel[:, j] * jnp.pad(x, ((0, 0), (j, 0), (0, 0)))[:, :t]
    return out


def ssm_scan(x, b, c, dt, a):
    """The state-space recurrence token by token.  ``x`` (N, T, H, P), ``b``
    and ``c`` (N, T, H, S) (each head's own group's), ``dt`` (N, T, H), ``a``
    (H,); returns ``y`` (N, T, H, P) without the ``D x`` term.  The state
    starts at zero for every sequence and head."""
    n, t, h, p = x.shape

    def token(state, xs):
        x, b, c, dt = xs
        state = jnp.exp(dt * a)[..., None, None] * state + \
            jnp.einsum('nhp,nhs->nhps', x * dt[..., None], b)
        return state, jnp.einsum('nhps,nhs->nhp', state, c)

    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (x, b, c, dt))
    state = jnp.zeros((n, h, p, b.shape[-1]), x.dtype)
    if t > SCAN_BLOCK and t % SCAN_BLOCK == 0:
        xs = tuple(v.reshape((t // SCAN_BLOCK, SCAN_BLOCK) + v.shape[1:])
                   for v in xs)
        out = jax.lax.scan(jax.checkpoint(block), state, xs)[1]
        out = out.reshape((t,) + out.shape[2:])
    else:
        out = block(state, xs)[1]
    return jnp.moveaxis(out, 0, 1)


def mamba(u, p, config):
    n, t, _ = u.shape
    heads, size = config['mamba_num_heads'], config['mamba_head_dim']
    groups, states = config['n_groups'], config['ssm_state_size']
    d = heads * size
    mixed = u @ p['in_weight'].T
    z, xbc, dt = (mixed[..., :d], mixed[..., d:2 * d + 2 * groups * states],
                  mixed[..., 2 * d + 2 * groups * states:])
    xbc = silu(causal_conv(xbc, p['ssm_conv_weight'], p['ssm_conv_bias']))
    x = xbc[..., :d].reshape(n, t, heads, size)

    def by_head(v):
        # head h reads the B and C of group h // (heads / groups)
        return jnp.repeat(v.reshape(n, t, groups, states), heads // groups,
                          axis=2)
    b = by_head(xbc[..., d:d + groups * states])
    c = by_head(xbc[..., d + groups * states:])
    dt = jax.nn.softplus(dt + p['ssm_dt_bias'])
    y = ssm_scan(x, b, c, dt, -jnp.exp(p['ssm_A_log'])) + \
        p['ssm_D'][:, None] * x
    y = y.reshape(n, t, d) * silu(z)
    y = y.reshape(n, t, groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) +
                          config['norm_eps'])
    return (y.reshape(n, t, d) * p['ssm_norm_gamma']) @ p['out_weight'].T


def causal_attention(q, k, v, scale):
    """(N, H, T, D) queries and (N, Hkv, T, D) keys and values; full masked
    softmax of ``scale q k^T``, one head at a time, query head i on
    key-value head i // (H / Hkv)."""
    n, h, t, d = q.shape
    k, v = (jnp.repeat(x, h // x.shape[1], axis=1) for x in (k, v))
    mask = jnp.tril(jnp.ones((t, t), bool))

    def one(args):
        qh, kh, vh = args
        scores = jnp.where(mask, (qh @ kh.T) * scale, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ vh

    out = jax.lax.map(jax.checkpoint(one),
                      (q.reshape(n * h, t, d), k.reshape(n * h, t, d),
                       v.reshape(n * h, t, d)))
    return out.reshape(n, h, t, d)


def attention(u, p, config):
    n, t, _ = u.shape
    heads, kv = config['num_attention_heads'], config['num_key_value_heads']

    def head_major(x, count):
        return x.reshape(n, t, count, -1).transpose(0, 2, 1, 3)
    q = head_major(u @ p['q_weight'].T, heads)
    k = head_major(u @ p['k_weight'].T, kv)
    v = head_major(u @ p['v_weight'].T, kv)
    out = causal_attention(q, k, v, q.shape[-1] ** -0.5)
    return out.transpose(0, 2, 1, 3).reshape(n, t, -1) @ p['o_weight'].T


def route(u, router, bias, config):
    """Chosen experts (T, k) and their weights (T, k)."""
    scores = jax.nn.sigmoid(u @ router.T)
    _, chosen = jax.lax.top_k(scores + bias, config['num_experts_per_tok'])
    weights = jnp.take_along_axis(scores, chosen, axis=1)
    if config.get('norm_topk_prob', True):
        weights = weights / (weights.sum(axis=-1, keepdims=True) + TOPK_EPS)
    return chosen, weights * config.get('routed_scaling_factor', 1.0)


def expert_layer(u, rows, router, bias, w1, w2, config):
    """The held experts' part of the routed layer: the router scores the
    tokens ``u`` (T, H), the experts take their ``rows`` (T, L) and give (T,
    L); and how many assignments each held expert received.  ``w1`` is
    (held, L, F), ``w2`` (held, F, L)."""
    first, count = config['experts_held']

    def block(both):
        u, x = both
        chosen, weights = route(u, router, bias, config)
        y = jnp.zeros_like(x)
        load = []
        for e in range(count):
            mine = chosen == first + e
            gate = jnp.sum(jnp.where(mine, weights, 0.0), axis=1)
            y = y + gate[:, None] * (relu2(x @ w1[e]) @ w2[e])
            load.append(jnp.sum(mine, axis=1))
        return y, jnp.stack(load, axis=1)

    # the two inputs have different widths: blocked side by side
    tokens = u.shape[0]
    if tokens <= TOKEN_BLOCK or tokens % TOKEN_BLOCK:
        y, load = block((u, rows))
    else:
        y, load = jax.lax.map(
            jax.checkpoint(block),
            tuple(v.reshape((tokens // TOKEN_BLOCK, TOKEN_BLOCK, -1))
                  for v in (u, rows)))
        y, load = (v.reshape((tokens,) + v.shape[2:]) for v in (y, load))
    return y, load.sum(axis=0)


def shared_expert(u, w1, w2):
    return _blocked(lambda x: relu2(x @ w1.T) @ w2.T, u)


def latent_moe(u, p, config):
    """The ``E`` mixer of tokens ``u`` (T, H) and the routed layer's load:
    the held experts' part through the latent's up-projection, plus the
    shared expert, once."""
    rows = u @ p['down_weight'].T
    y, load = expert_layer(u, rows, p['router_weight'], p['moe_expert_bias'],
                           p['experts_w1_weight'], p['experts_w2_weight'],
                           config)
    return y @ p['up_weight'].T + shared_expert(
        u, p['shared_w1_weight'], p['shared_w2_weight']), load


def layer(x, p, kind, config):
    """One block.  ``p`` holds the block's arrays by the part of their names
    after ``l<index>_``.  Returns ``x'`` and the routed layer's load (None
    for ``M`` and ``*``)."""
    n, t, _ = x.shape
    u = rms_norm(x, p['norm_gamma'], config['norm_eps'])
    load = None
    if kind == 'M':
        mixed = mamba(u, p, config)
    elif kind == '*':
        mixed = attention(u, p, config)
    elif kind == 'E':
        mixed, load = latent_moe(u.reshape(n * t, -1), p, config)
        mixed = mixed.reshape(n, t, -1)
    else:
        raise ValueError('unknown block %r' % kind)
    return x + mixed, load


def forward(params, tokens, config):
    """Log-probabilities (N * T, V) of the next token over the vocabulary's
    rows held here, and each routed layer's load (block index -> (held,)
    assignments).  ``tokens`` is (N, T) whole numbers."""
    n, t = tokens.shape
    load = {}
    with jax.default_matmul_precision('highest'):
        params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        x = params['embed_weight'][jnp.asarray(tokens).astype(jnp.int32)]
        for i, kind in enumerate(config['pattern']):
            prefix = 'l%d_' % i
            mine = {k[len(prefix):]: params[k]
                    for k in layer_param_names(i, kind)}
            x, load_i = jax.checkpoint(
                lambda x, p, kind=kind: layer(x, p, kind, config))(x, mine)
            if load_i is not None:
                load[i] = load_i
        z = rms_norm(x, params['final_norm_gamma'], config['norm_eps']) \
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
    gradient: not to ``*_A_log``, ``*_D``, ``*_dt_bias`` and the
    convolution's ``*_bias``."""
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
