"""The plain reference: a symbol's forward pass in float32 ``jax.numpy``.

Walks the symbol's JSON node by node and computes each operator from its
published definition (MXNet 0.9 ``src/operator``), in float32 under
``jax.default_matmul_precision("highest")``, with no kernel, no fusion
pass, no layout trick and nothing imported from ``mxnet_tpu.ops``.  It is
what ``correct`` compares the program against, so it must stay
independent of the program: a later PR may add an operator here (in a
reference file of its own, named by its configuration) but may not make
this one call the code under test.

Operators: Convolution, BatchNorm (batch statistics in training, moving
statistics in inference), Activation, Pooling, Concat, elementwise add,
Flatten, FullyConnected, SoftmaxOutput.
"""
import ast
import json

import jax
import jax.numpy as jnp


def _attr(attrs, key, default=None):
    if key not in attrs:
        return default
    value = attrs[key]
    if isinstance(value, str):
        try:
            return ast.literal_eval(value)
        except (ValueError, SyntaxError):
            return value
    return value


def _pair(value, default):
    if value is None:
        return (default, default)
    if isinstance(value, int):
        return (value, value)
    return tuple(int(v) for v in value)


def _convolution(attrs, x, weight, bias=None):
    stride = _pair(_attr(attrs, 'stride'), 1)
    pad = _pair(_attr(attrs, 'pad'), 0)
    pad_hi = _pair(_attr(attrs, 'pad_hi'), None) \
        if _attr(attrs, 'pad_hi') else pad
    dilate = _pair(_attr(attrs, 'dilate'), 1)
    out = jax.lax.conv_general_dilated(
        x, weight, window_strides=stride,
        padding=list(zip(pad, pad_hi)), rhs_dilation=dilate,
        dimension_numbers=('NCHW', 'OIHW', 'NCHW'),
        feature_group_count=int(_attr(attrs, 'num_group', 1)))
    if bias is not None:
        out = out + bias.reshape(1, -1, 1, 1)
    return out


def _batch_norm(attrs, is_train, x, gamma, beta, moving_mean, moving_var):
    """Returns the output and the (mean, variance) it normalised with."""
    eps = float(_attr(attrs, 'eps', 1e-3))
    if bool(_attr(attrs, 'fix_gamma', True)):
        gamma = jnp.ones_like(gamma)
    if is_train and not bool(_attr(attrs, 'use_global_stats', False)):
        mean = jnp.mean(x, axis=(0, 2, 3))
        var = jnp.mean(jnp.square(x - mean.reshape(1, -1, 1, 1)),
                       axis=(0, 2, 3))                # biased, as MXNet
    else:
        mean, var = moving_mean, moving_var
    shape = (1, -1, 1, 1)
    out = (x - mean.reshape(shape)) / jnp.sqrt(var.reshape(shape) + eps) \
        * gamma.reshape(shape) + beta.reshape(shape)
    return out, (mean, var)


def _pooling(attrs, x):
    pool_type = _attr(attrs, 'pool_type', 'max')
    if bool(_attr(attrs, 'global_pool', False)):
        reduce = jnp.max if pool_type == 'max' else jnp.mean
        return reduce(x, axis=(2, 3), keepdims=True)
    kernel = _pair(_attr(attrs, 'kernel'), 1)
    stride = _pair(_attr(attrs, 'stride'), 1)
    pad = _pair(_attr(attrs, 'pad'), 0)
    full = _attr(attrs, 'pooling_convention', 'valid') == 'full'
    padding = [(0, 0), (0, 0)]
    for size, k, s, p in zip(x.shape[2:], kernel, stride, pad):
        span = size + 2 * p - k
        out = (-(-span // s) if full else span // s) + 1
        padding.append((p, max((out - 1) * s + k - size - p, p)))
    window, strides = (1, 1) + kernel, (1, 1) + stride
    if pool_type == 'max':
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, window,
                                     strides, padding)
    total = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides,
                                  padding)
    # MXNet 0.9 divides by the whole window, padding included
    return total / float(kernel[0] * kernel[1]) if pool_type == 'avg' \
        else total


def _activation(attrs, x):
    kind = _attr(attrs, 'act_type', 'relu')
    return {'relu': lambda v: jnp.maximum(v, 0.0), 'tanh': jnp.tanh,
            'sigmoid': jax.nn.sigmoid}[kind](x)


def forward(symbol_json, arrays, is_train):
    """Softmax outputs of the symbol (its JSON text) for the inputs and
    parameters in ``arrays`` (name -> array: data, arguments and
    auxiliary states),
    and the statistics every BatchNorm normalised with, by node name.

    ``is_train`` picks BatchNorm's batch statistics (the forward pass of
    a training step) or its moving statistics (inference)."""
    graph = json.loads(symbol_json)
    nodes = graph['nodes']
    values, bn_stats = {}, {}
    with jax.default_matmul_precision('highest'):
        for index, node in enumerate(nodes):
            op, attrs = node['op'], node.get('attrs', {})
            if op == 'null':
                if node['name'] in arrays:
                    values[index] = jnp.asarray(arrays[node['name']],
                                                jnp.float32)
                continue                     # e.g. the label: not needed
            ins = [values[i[0]] for i in node['inputs'] if i[0] in values]
            if op == 'Convolution':
                out = _convolution(attrs, *ins)
            elif op == 'BatchNorm':
                out, bn_stats[node['name']] = _batch_norm(attrs, is_train,
                                                          *ins)
            elif op == 'Activation':
                out = _activation(attrs, ins[0])
            elif op == 'Pooling':
                out = _pooling(attrs, ins[0])
            elif op == 'Concat':
                out = jnp.concatenate(ins, axis=int(_attr(attrs, 'dim', 1)))
            elif op in ('_plus', '_Plus', 'elemwise_add', '_add'):
                out = ins[0] + ins[1]
            elif op == 'Flatten':
                out = ins[0].reshape(ins[0].shape[0], -1)
            elif op == 'FullyConnected':
                out = jnp.dot(ins[0].reshape(ins[0].shape[0], -1),
                              ins[1].T)
                if not bool(_attr(attrs, 'no_bias', False)):
                    out = out + ins[2]
            elif op == 'SoftmaxOutput':
                out = jax.nn.softmax(ins[0], axis=-1)
            else:
                raise NotImplementedError(
                    'benchmark/reference.py has no definition of %r '
                    '(node %s)' % (op, node['name']))
            values[index] = out
    return values[graph['heads'][0][0]], bn_stats


forward_jit = jax.jit(forward, static_argnums=(0, 2))


def _log(prob):
    import numpy as np
    return np.log(np.maximum(np.asarray(prob, np.float64), 1e-30))


def log_prob_error(prob, prob_reference):
    """How far softmax outputs lie from the reference's: the root mean
    square of the difference of the log-probabilities, over every row
    and every class, as a share of the standard deviation of the
    reference's log-probabilities.  0 is agreement; an output that says
    the same for every class reads 1 or more.  In float64 on the host."""
    import numpy as np
    got, want = _log(prob), _log(prob_reference)
    if got.shape != want.shape:
        raise ValueError('outputs of shape %s against a reference of %s'
                         % (got.shape, want.shape))
    return float(np.sqrt(np.mean(np.square(got - want))) / want.std())


def row_agreement(prob, prob_reference):
    """Whether each row got its own answer: the correlation between the
    outputs' and the reference's log-probabilities once each has lost,
    class by class, its mean over the rows, which is what every row
    shares.  1 is agreement; the right answers in the wrong rows read
    about 0, and so does an output that is the same for every row (no
    variation to correlate).  ``log_prob_error`` alone misses both where
    the rows differ little, as an untrained network's do."""
    import numpy as np
    got, want = _log(prob), _log(prob_reference)
    got = got - got.mean(axis=0, keepdims=True)
    want = want - want.mean(axis=0, keepdims=True)
    norm = np.sqrt(np.sum(got * got) * np.sum(want * want))
    return float(np.sum(got * want) / norm) if norm > 0 else 0.0


def cross_entropy(prob, label):
    """Mean negative log-likelihood of ``label`` (class ids) under the
    softmax outputs ``prob``, in float64 on the host."""
    import numpy as np
    prob = np.asarray(prob, np.float64)
    picked = prob[np.arange(prob.shape[0]), np.asarray(label).astype(int)]
    return float(-np.log(picked + 1e-12).mean())
