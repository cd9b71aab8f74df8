"""Neural-network layer operators.

TPU-native equivalents of the reference's legacy stateful layers
(``src/operator/*-inl.h``: ``fully_connected-inl.h``,
``convolution-inl.h``, ``pooling-inl.h``, ``batch_norm-inl.h:319``,
``dropout-inl.h``, ``softmax_output-inl.h:381``, ``concat-inl.h``,
``slice_channel-inl.h``, ``lrn-inl.h``, ``l2_normalization-inl.h:290``,
``instance_norm-inl.h``, ``upsampling-inl.h:318``, ``crop-inl.h``,
``sequence_{last,mask,reverse}-inl.h``) and their cuDNN fast paths
(``src/operator/cudnn_*-inl.h``).  There is no cpu/cudnn split here: each
layer is a single JAX expression lowered by XLA onto the MXU; the cuDNN
autotune machinery (``cudnn_convolution-inl.h:638``) is subsumed by XLA's
implicit convolution algorithm selection.

Layers with learned parameters implement ``complete_shapes`` so MXNet-style
bidirectional shape inference (``simple_bind``) can derive weight shapes
from data shapes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register, register_simple, alias


def _complete(shapes, idx, value):
    if shapes[idx] is None:
        shapes[idx] = tuple(int(v) for v in value)
    return shapes


def _tup(v, n=2, default=1):
    if v is None or v == ():
        return (default,) * n
    if isinstance(v, int):
        return (v,) * n
    return tuple(int(x) for x in v)


# ---------------------------------------------------------------------------
# FullyConnected (fully_connected-inl.h).  weight layout (num_hidden, in),
# matching the reference so checkpoints interchange.
# ---------------------------------------------------------------------------

def _fc_apply(attrs, inputs, is_train, rng):
    no_bias = bool(attrs.get('no_bias', False))
    data = inputs[0]
    weight = inputs[1]
    x = data.reshape(data.shape[0], -1)
    out = jnp.dot(x, weight.T)
    if not no_bias:
        out = out + inputs[2]
    return [out], {}


def _fc_complete(attrs, in_shapes):
    num_hidden = int(attrs['num_hidden'])
    data_shape = in_shapes[0]
    if data_shape is not None:
        in_dim = int(np.prod(data_shape[1:]))
        _complete(in_shapes, 1, (num_hidden, in_dim))
    if not attrs.get('no_bias', False):
        _complete(in_shapes, 2, (num_hidden,))
    return in_shapes


register('FullyConnected', _fc_apply,
         input_names=lambda attrs: (['data', 'weight'] if attrs.get('no_bias', False)
                                    else ['data', 'weight', 'bias']),
         num_outputs=lambda attrs: 1,
         complete_shapes=_fc_complete,
         attr_defaults={'no_bias': False}, hint='fullyconnected')


# ---------------------------------------------------------------------------
# Convolution / Deconvolution (convolution-inl.h / deconvolution-inl.h).
# NCHW in/out layout like the reference; lowered to
# lax.conv_general_dilated which XLA maps straight onto the MXU.
# ---------------------------------------------------------------------------

def _conv_apply(attrs, inputs, is_train, rng):
    data, weight = inputs[0], inputs[1]
    no_bias = bool(attrs.get('no_bias', False))
    kernel = tuple(attrs['kernel'])
    nd = len(kernel)
    stride = _tup(attrs.get('stride'), nd)
    dilate = _tup(attrs.get('dilate'), nd)
    pad = _tup(attrs.get('pad'), nd, default=0)
    # Internal extension over the reference Convolution: 'pad_hi' gives
    # the high-side padding when it differs from 'pad' (asymmetric
    # padding, used by the space-to-depth ResNet stem rewrite —
    # models/resnet.py).  Absent → symmetric, reference semantics.
    pad_hi = attrs.get('pad_hi')
    pad_pairs = [(p, q) for p, q in zip(
        pad, _tup(pad_hi, nd) if pad_hi else pad)]
    groups = int(attrs.get('num_group', 1))
    dn = jax.lax.conv_dimension_numbers(
        data.shape, weight.shape,
        ('NCHW', 'OIHW', 'NCHW') if nd == 2 else ('NCW', 'OIW', 'NCW'))
    out = jax.lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=pad_pairs, lhs_dilation=(1,) * nd,
        rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=groups,
        preferred_element_type=None)
    if not no_bias:
        bias = inputs[2]
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return [out], {}


def _conv_complete(attrs, in_shapes):
    kernel = tuple(attrs['kernel'])
    num_filter = int(attrs['num_filter'])
    groups = int(attrs.get('num_group', 1))
    data_shape = in_shapes[0]
    if data_shape is not None:
        _complete(in_shapes, 1,
                  (num_filter, data_shape[1] // groups) + kernel)
    if not attrs.get('no_bias', False):
        _complete(in_shapes, 2, (num_filter,))
    return in_shapes


register('Convolution', _conv_apply,
         input_names=lambda attrs: (['data', 'weight'] if attrs.get('no_bias', False)
                                    else ['data', 'weight', 'bias']),
         num_outputs=lambda attrs: 1,
         complete_shapes=_conv_complete,
         attr_defaults={'no_bias': False, 'num_group': 1, 'stride': None,
                        'dilate': None, 'pad': None, 'workspace': 1024,
                        'cudnn_tune': None, 'cudnn_off': False, 'layout': None},
         hint='convolution')


def _deconv_apply(attrs, inputs, is_train, rng):
    data, weight = inputs[0], inputs[1]
    no_bias = bool(attrs.get('no_bias', True))
    kernel = tuple(attrs['kernel'])
    nd = len(kernel)
    stride = _tup(attrs.get('stride'), nd)
    pad = _tup(attrs.get('pad'), nd, default=0)
    adj = _tup(attrs.get('adj'), nd, default=0)
    dilate = _tup(attrs.get('dilate'), nd)
    groups = int(attrs.get('num_group', 1))
    # Transposed conv as an input-dilated conv with the spatially
    # flipped kernel: out = (in-1)*stride - 2*pad + d*(k-1)+1 + adj
    # (deconvolution-inl.h output-shape formula).  Weight layout is the
    # reference's (in_channels, num_filter/groups, *kernel).
    ek = [d * (k - 1) + 1 for k, d in zip(kernel, dilate)]
    tshape = attrs.get('target_shape')
    if tshape:
        # reference: pad derived so the output hits target_shape
        tshape = _tup(tshape, nd)
        pad = tuple(((data.shape[2 + i] - 1) * stride[i] + ek[i]
                     + adj[i] - tshape[i]) // 2 for i in range(nd))
    spatial = tuple(range(2, 2 + nd))
    w = jnp.flip(weight, axis=spatial)
    if groups > 1:
        # (g*cin_g, cout_g, *k) -> (cin_g, g*cout_g, *k): XLA's grouped
        # conv wants O blocked group-major, I per-group
        cin_g = w.shape[0] // groups
        w = w.reshape((groups, cin_g) + w.shape[1:]) \
             .swapaxes(0, 1) \
             .reshape((cin_g, groups * w.shape[1]) + w.shape[2:])
    dn_spec = ('NCHW', 'IOHW', 'NCHW') if nd == 2 else \
        ('NCW', 'IOW', 'NCW')
    padding = [(e - 1 - p, e - 1 - p + a)
               for e, p, a in zip(ek, pad, adj)]
    dn = jax.lax.conv_dimension_numbers(data.shape, w.shape, dn_spec)
    out = jax.lax.conv_general_dilated(
        data, w, window_strides=(1,) * nd, padding=padding,
        lhs_dilation=stride, rhs_dilation=dilate,
        dimension_numbers=dn, feature_group_count=groups)
    if not no_bias:
        out = out + inputs[2].reshape((1, -1) + (1,) * nd)
    return [out], {}


def _deconv_complete(attrs, in_shapes):
    kernel = tuple(attrs['kernel'])
    num_filter = int(attrs['num_filter'])
    groups = int(attrs.get('num_group', 1))
    data_shape = in_shapes[0]
    if data_shape is not None:
        _complete(in_shapes, 1,
                  (data_shape[1], num_filter // groups) + kernel)
    if not attrs.get('no_bias', True):
        _complete(in_shapes, 2, (num_filter,))
    return in_shapes


register('Deconvolution', _deconv_apply,
         input_names=lambda attrs: (['data', 'weight'] if attrs.get('no_bias', True)
                                    else ['data', 'weight', 'bias']),
         num_outputs=lambda attrs: 1,
         complete_shapes=_deconv_complete,
         attr_defaults={'no_bias': True, 'num_group': 1, 'stride': None,
                        'pad': None, 'adj': None, 'dilate': None,
                        'target_shape': None, 'workspace': 1024,
                        'cudnn_tune': None, 'layout': None},
         hint='deconvolution')


# ---------------------------------------------------------------------------
# Pooling (pooling-inl.h:334).  reduce_window handles both conventions;
# avg counts padded cells like mshadow's pool (count-include-pad).
# ---------------------------------------------------------------------------

def _pool_out_dim(x, k, p, s, convention):
    if convention == 'full':
        return int(np.ceil(float(x + 2 * p - k) / s)) + 1
    return (x + 2 * p - k) // s + 1


def _pooling_apply(attrs, inputs, is_train, rng):
    data = inputs[0]
    pool_type = attrs.get('pool_type', 'max')
    global_pool = bool(attrs.get('global_pool', False))
    nd = data.ndim - 2
    if global_pool:
        if pool_type == 'max':
            out = jnp.max(data, axis=tuple(range(2, data.ndim)), keepdims=True)
        else:
            out = jnp.mean(data, axis=tuple(range(2, data.ndim)), keepdims=True)
        return [out], {}
    kernel = _tup(attrs['kernel'], nd)
    stride = _tup(attrs.get('stride'), nd)
    pad = _tup(attrs.get('pad'), nd, default=0)
    convention = attrs.get('pooling_convention', 'valid')
    # Right-pad so reduce_window emits exactly the convention's output size.
    pads = []
    for i in range(nd):
        out_d = _pool_out_dim(data.shape[2 + i], kernel[i], pad[i], stride[i],
                              convention)
        needed = (out_d - 1) * stride[i] + kernel[i] - data.shape[2 + i]
        pads.append((pad[i], max(needed - pad[i], pad[i])))
    window = (1, 1) + kernel
    strides = (1, 1) + stride
    padding = [(0, 0), (0, 0)] + pads
    if pool_type == 'max':
        # The compiler's own pair: reduce_window, whose gradient is
        # select_and_scatter (a window's gradient goes to its first
        # maximal element).  Measured on the v5e against hand-written
        # forms that save an int8 argmax map (PERF.md section 6,
        # PR 27): the step is 7 to 12 ms shorter with this one.
        init = -jnp.inf
        out = jax.lax.reduce_window(data, init, jax.lax.max, window, strides,
                                    padding)
    else:
        out = jax.lax.reduce_window(data, 0.0, jax.lax.add, window, strides,
                                    padding)
        if pool_type == 'avg':
            out = out / float(np.prod(kernel))
    return [out], {}


register('Pooling', _pooling_apply,
         input_names=lambda attrs: ['data'],
         num_outputs=lambda attrs: 1,
         attr_defaults={'pool_type': 'max', 'global_pool': False,
                        'kernel': (1, 1), 'stride': None, 'pad': None,
                        'pooling_convention': 'valid', 'cudnn_off': False},
         hint='pooling')


# ---------------------------------------------------------------------------
# Activations (activation-inl.h, leaky_relu-inl.h, softmax_activation-inl.h)
# ---------------------------------------------------------------------------

_ACTS = {'relu': jax.nn.relu, 'sigmoid': jax.nn.sigmoid, 'tanh': jnp.tanh,
         'softrelu': jax.nn.softplus, 'silu': jax.nn.silu}

register_simple('Activation',
                lambda x, act_type='relu': _ACTS[act_type](x),
                attr_defaults={'act_type': 'relu'}, hint='activation')


def _leaky_relu_apply(attrs, inputs, is_train, rng):
    act_type = attrs.get('act_type', 'leaky')
    slope = float(attrs.get('slope', 0.25))
    data = inputs[0]
    if act_type == 'leaky':
        out = jnp.where(data > 0, data, slope * data)
    elif act_type == 'elu':
        out = jnp.where(data > 0, data, slope * (jnp.exp(data) - 1.0))
    elif act_type == 'prelu':
        gamma = inputs[1].reshape((1, -1) + (1,) * (data.ndim - 2))
        out = jnp.where(data > 0, data, gamma * data)
    elif act_type == 'rrelu':
        if is_train:
            lower = float(attrs.get('lower_bound', 0.125))
            upper = float(attrs.get('upper_bound', 0.334))
            r = jax.random.uniform(rng, data.shape, data.dtype, lower, upper)
            out = jnp.where(data > 0, data, r * data)
        else:
            mid = (float(attrs.get('lower_bound', 0.125)) +
                   float(attrs.get('upper_bound', 0.334))) / 2.0
            out = jnp.where(data > 0, data, mid * data)
    else:
        raise ValueError('unknown act_type %s' % act_type)
    return [out], {}


def _leaky_complete(attrs, in_shapes):
    if attrs.get('act_type', 'leaky') == 'prelu' and in_shapes[0] is not None:
        _complete(in_shapes, 1, (in_shapes[0][1],))
    return in_shapes


def _leaky_relu_var_attrs(attrs, input_name):
    if input_name == 'gamma':
        # prelu slope parameter defaults to the op's slope value
        # (leaky_relu-inl.h slope=0.25 via FSetInputVariableAttrs)
        import json as _json
        return {'__init__': _json.dumps(
            ['constant', {'value': float(attrs.get('slope', 0.25))}])}
    return None


register('LeakyReLU', _leaky_relu_apply,
         input_var_attrs=_leaky_relu_var_attrs,
         input_names=lambda attrs: (['data', 'gamma']
                                    if attrs.get('act_type', 'leaky') == 'prelu'
                                    else ['data']),
         num_outputs=lambda attrs: 1,
         complete_shapes=_leaky_complete,
         takes_rng=True,
         attr_defaults={'act_type': 'leaky', 'slope': 0.25,
                        'lower_bound': 0.125, 'upper_bound': 0.334},
         hint='leakyrelu')

register_simple('softmax', lambda x, axis=-1, temperature=1.0:
                jax.nn.softmax(x / temperature, axis=int(axis)),
                attr_defaults={'axis': -1, 'temperature': 1.0})
register_simple('log_softmax', lambda x, axis=-1:
                jax.nn.log_softmax(x, axis=int(axis)),
                attr_defaults={'axis': -1})
register_simple('SoftmaxActivation',
                lambda x, mode='instance': (
                    jax.nn.softmax(x.reshape(x.shape[0], -1), axis=-1)
                    .reshape(x.shape) if mode == 'instance'
                    else jax.nn.softmax(x, axis=1)),
                attr_defaults={'mode': 'instance'}, hint='softmaxactivation')


# ---------------------------------------------------------------------------
# Output/loss layers.  The reference defines these layers' *backward* to
# inject the loss gradient directly, ignoring any incoming head gradient
# (softmax_output-inl.h Backward; regression_output-inl.h).  custom_vjp
# reproduces exactly that contract in functional form.
# ---------------------------------------------------------------------------

def _softmax_output_grad(prob, label, attrs):
    multi = bool(attrs.get('multi_output', False))
    grad_scale = float(attrs.get('grad_scale', 1.0))
    use_ignore = bool(attrs.get('use_ignore', False))
    ignore_label = float(attrs.get('ignore_label', -1))
    normalization = attrs.get('normalization', 'null')
    if multi:
        # data (N, C, ...), label (N, ...)
        n_class = prob.shape[1]
        onehot = jax.nn.one_hot(label.astype(jnp.int32), n_class, axis=1,
                                dtype=prob.dtype)
    else:
        if label.ndim == prob.ndim:
            onehot = label.astype(prob.dtype)
        else:
            onehot = jax.nn.one_hot(label.astype(jnp.int32), prob.shape[-1],
                                    dtype=prob.dtype)
    grad = prob - onehot
    valid = None
    if use_ignore and label.ndim < prob.ndim:
        mask = (label != ignore_label).astype(prob.dtype)
        if multi:
            grad = grad * mask[:, None]
        else:
            grad = grad * mask.reshape(mask.shape + (1,) * (grad.ndim - mask.ndim))
        valid = jnp.sum(mask)
    scale = grad_scale
    if normalization == 'batch':
        grad = grad / prob.shape[0]
    elif normalization == 'valid' and valid is not None:
        grad = grad / jnp.maximum(valid, 1.0)
    return grad * scale


def _softmax_output_apply(attrs, inputs, is_train, rng):
    data, label = inputs[0], inputs[1]
    multi = bool(attrs.get('multi_output', False))
    preserve = bool(attrs.get('preserve_shape', False))

    @jax.custom_vjp
    def f(d, l):
        if multi:
            return jax.nn.softmax(d, axis=1)
        if preserve or d.ndim <= 2:
            return jax.nn.softmax(d, axis=-1)
        return jax.nn.softmax(d.reshape(d.shape[0], -1),
                              axis=-1).reshape(d.shape)

    def fwd(d, l):
        p = f(d, l)
        return p, (p, l)

    def bwd(res, g):
        p, l = res
        # Reference semantics: head gradient is ignored; loss grad injected.
        return (_softmax_output_grad(p, l, attrs).astype(p.dtype),
                jnp.zeros_like(l))

    f.defvjp(fwd, bwd)
    return [f(data, label)], {}


def _softmax_output_complete(attrs, in_shapes):
    d = in_shapes[0]
    if d is not None and in_shapes[1] is None:
        if bool(attrs.get('multi_output', False)):
            in_shapes[1] = (d[0],) + tuple(d[2:])
        else:
            in_shapes[1] = tuple(d[:-1]) if len(d) > 1 else (d[0],)
    return in_shapes


register('SoftmaxOutput', _softmax_output_apply,
         input_names=lambda attrs: ['data', 'label'],
         num_outputs=lambda attrs: 1,
         complete_shapes=_softmax_output_complete,
         attr_defaults={'grad_scale': 1.0, 'ignore_label': -1.0,
                        'multi_output': False, 'use_ignore': False,
                        'preserve_shape': False, 'normalization': 'null',
                        'out_grad': False},
         hint='softmaxoutput')
alias('Softmax', 'SoftmaxOutput')


def _make_regression(link, grad_fn, name, hint):
    def apply_fn(attrs, inputs, is_train, rng):
        data, label = inputs[0], inputs[1]
        grad_scale = float(attrs.get('grad_scale', 1.0))

        @jax.custom_vjp
        def f(d, l):
            return link(d)

        def fwd(d, l):
            return link(d), (link(d), l)

        def bwd(res, g):
            out, l = res
            # reference divides by outputs-per-sample (regression_output-inl.h)
            num = float(np.prod(out.shape[1:])) if out.ndim > 1 else 1.0
            grad = grad_fn(out, l.reshape(out.shape)) * (grad_scale / num)
            return grad.astype(out.dtype), jnp.zeros_like(l)

        f.defvjp(fwd, bwd)
        return [f(data, label)], {}

    def complete(attrs, in_shapes):
        if in_shapes[0] is not None and in_shapes[1] is None:
            in_shapes[1] = tuple(in_shapes[0])
        return in_shapes

    register(name, apply_fn,
             input_names=lambda attrs: ['data', 'label'],
             num_outputs=lambda attrs: 1,
             complete_shapes=complete,
             attr_defaults={'grad_scale': 1.0}, hint=hint)


_make_regression(lambda x: x, lambda o, l: o - l,
                 'LinearRegressionOutput', 'linearregressionoutput')
_make_regression(lambda x: x, lambda o, l: jnp.sign(o - l),
                 'MAERegressionOutput', 'maeregressionoutput')
_make_regression(jax.nn.sigmoid, lambda o, l: o - l,
                 'LogisticRegressionOutput', 'logisticregressionoutput')


def _svm_output_apply(attrs, inputs, is_train, rng):
    data, label = inputs[0], inputs[1]
    margin = float(attrs.get('margin', 1.0))
    reg_coef = float(attrs.get('regularization_coefficient', 1.0))
    use_linear = bool(attrs.get('use_linear', False))

    @jax.custom_vjp
    def f(d, l):
        return d

    def fwd(d, l):
        return d, (d, l)

    def bwd(res, g):
        d, l = res
        lab = jax.nn.one_hot(l.astype(jnp.int32), d.shape[1], dtype=d.dtype)
        score_correct = jnp.sum(d * lab, axis=1, keepdims=True)
        if use_linear:
            viol = ((d - score_correct + margin) > 0).astype(d.dtype)
        else:
            viol = jnp.maximum(d - score_correct + margin, 0.0)
        viol = viol * (1.0 - lab)
        grad = viol - lab * jnp.sum(viol, axis=1, keepdims=True)
        return (reg_coef * grad).astype(d.dtype), jnp.zeros_like(l)

    f.defvjp(fwd, bwd)
    return [f(data, label)], {}


def _svm_complete(attrs, in_shapes):
    if in_shapes[0] is not None and in_shapes[1] is None:
        in_shapes[1] = (in_shapes[0][0],)
    return in_shapes


register('SVMOutput', _svm_output_apply,
         input_names=lambda attrs: ['data', 'label'],
         num_outputs=lambda attrs: 1,
         complete_shapes=_svm_complete,
         attr_defaults={'margin': 1.0, 'regularization_coefficient': 1.0,
                        'use_linear': False},
         hint='svmoutput')


# ---------------------------------------------------------------------------
# BatchNorm (batch_norm-inl.h:319 / cudnn_batch_norm-inl.h).  Aux moving
# stats are functional here: updates are returned and written back by the
# executor, never differentiated (the reference likewise excludes aux from
# gradient computation).
# ---------------------------------------------------------------------------

def batch_norm_stats(data, moving_mean, moving_var, axes, momentum,
                     use_batch_stats):
    """Shared stats step: returns ``(mean, var, aux_updates)``.

    One-pass stats: E[x] and E[x^2] are independent sibling reductions,
    so XLA multi-output-fuses them into a SINGLE read of the
    activation.  jnp.var's (x - mean)^2 form needs mean first — a
    second full HBM pass per BN layer, which on a memory-bound graph
    (ResNet-50 bf16 train) is ~15% of step traffic.  Accumulate in f32
    (cuDNN's discipline) and clamp the E[x^2]-E[x]^2 cancellation at
    zero.

    Also the stats step of the BN->relu->conv fusion pass (fuse.py),
    whose numerics must match BatchNorm exactly — keep ONE copy.
    """
    if use_batch_stats:
        x32 = data.astype(jnp.float32)
        mean32 = jnp.mean(x32, axis=axes)
        var32 = jnp.maximum(
            jnp.mean(jnp.square(x32), axis=axes) - jnp.square(mean32),
            0.0)
        aux_updates = {
            'moving_mean': jax.lax.stop_gradient(
                momentum * moving_mean + (1 - momentum) * mean32),
            'moving_var': jax.lax.stop_gradient(
                momentum * moving_var + (1 - momentum) * var32),
        }
        return (mean32.astype(data.dtype), var32.astype(data.dtype),
                aux_updates)
    # moving stats are kept f32; compute in the data dtype (bf16 path)
    return (jax.lax.stop_gradient(moving_mean).astype(data.dtype),
            jax.lax.stop_gradient(moving_var).astype(data.dtype), {})


def _batch_norm_apply(attrs, inputs, is_train, rng):
    data, gamma, beta, moving_mean, moving_var = inputs
    eps = float(attrs.get('eps', 1e-3))
    momentum = float(attrs.get('momentum', 0.9))
    fix_gamma = bool(attrs.get('fix_gamma', True))
    use_global = bool(attrs.get('use_global_stats', False))
    output_mean_var = bool(attrs.get('output_mean_var', False))
    axes = (0,) + tuple(range(2, data.ndim))
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    mean, var, aux_updates = batch_norm_stats(
        data, moving_mean, moving_var, axes, momentum,
        is_train and not use_global)
    inv = jax.lax.rsqrt(var.reshape(bshape) + eps)
    # normalize in f32 (stats precision) but emit the INPUT dtype:
    # under bf16 compute the f32-promoted output would otherwise
    # materialize every BN activation and its vjp residual at 2x the
    # bytes on the HBM-bound train path (round-5 audit: 8x256x56x56
    # f32 tensors x36 in the lowered step)
    out = ((data - mean.reshape(bshape)) * inv * g.reshape(bshape)
           + beta.reshape(bshape)).astype(data.dtype)
    outs = [out]
    if output_mean_var:
        outs += [mean, jax.lax.rsqrt(var + eps)]
    return outs, aux_updates


def _bn_complete(attrs, in_shapes):
    if in_shapes[0] is not None:
        c = in_shapes[0][1]
        for i in (1, 2):
            _complete(in_shapes, i, (c,))
    return in_shapes


def _bn_aux_shapes(attrs, in_shapes):
    c = in_shapes[0][1] if in_shapes[0] is not None else None
    return [(c,), (c,)] if c is not None else [None, None]


register('BatchNorm', _batch_norm_apply,
         input_names=lambda attrs: ['data', 'gamma', 'beta'],
         num_outputs=lambda attrs: 3 if attrs.get('output_mean_var', False) else 1,
         aux_names=lambda attrs: ['moving_mean', 'moving_var'],
         complete_shapes=_bn_complete,
         attr_defaults={'eps': 1e-3, 'momentum': 0.9, 'fix_gamma': True,
                        'use_global_stats': False, 'output_mean_var': False},
         hint='batchnorm')
register('CuDNNBatchNorm', _batch_norm_apply,
         input_names=lambda attrs: ['data', 'gamma', 'beta'],
         num_outputs=lambda attrs: 1,
         aux_names=lambda attrs: ['moving_mean', 'moving_var'],
         complete_shapes=_bn_complete,
         attr_defaults={'eps': 1e-3, 'momentum': 0.9, 'fix_gamma': True,
                        'use_global_stats': False},
         hint='cudnnbatchnorm')


# ---------------------------------------------------------------------------
# InstanceNorm / L2Normalization / LRN
# ---------------------------------------------------------------------------

def _instance_norm_apply(attrs, inputs, is_train, rng):
    data, gamma, beta = inputs
    eps = float(attrs.get('eps', 1e-3))
    axes = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=axes, keepdims=True)
    var = jnp.var(data, axis=axes, keepdims=True)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    out = (data - mean) * jax.lax.rsqrt(var + eps)
    return [out * gamma.reshape(bshape) + beta.reshape(bshape)], {}


register('InstanceNorm', _instance_norm_apply,
         input_names=lambda attrs: ['data', 'gamma', 'beta'],
         num_outputs=lambda attrs: 1,
         complete_shapes=_bn_complete,
         attr_defaults={'eps': 1e-3}, hint='instancenorm')


def _l2_normalization(x, eps=1e-10, mode='instance'):
    if mode == 'instance':
        norm = jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)),
                                axis=1) + eps)
        return x / norm.reshape((-1,) + (1,) * (x.ndim - 1))
    if mode == 'channel':
        norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=1, keepdims=True) + eps)
        return x / norm
    if mode == 'spatial':
        axes = tuple(range(2, x.ndim))
        norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes, keepdims=True) + eps)
        return x / norm
    raise ValueError(mode)


register_simple('L2Normalization', _l2_normalization,
                attr_defaults={'eps': 1e-10, 'mode': 'instance'},
                hint='l2normalization')


def _lrn(x, nsize=5, alpha=1e-4, beta=0.75, knorm=2.0):
    nsize = int(nsize)
    sq = jnp.square(x)
    half = nsize // 2
    # sum over a channel window: pad C then reduce_window along axis 1
    window = (1, nsize) + (1,) * (x.ndim - 2)
    ssum = jax.lax.reduce_window(sq, 0.0, jax.lax.add, window,
                                 (1,) * x.ndim,
                                 [(0, 0), (half, half)] + [(0, 0)] * (x.ndim - 2))
    return x / jnp.power(knorm + (alpha / nsize) * ssum, beta)


register_simple('LRN', _lrn,
                attr_defaults={'nsize': 5, 'alpha': 1e-4, 'beta': 0.75,
                               'knorm': 2.0}, hint='lrn')


# ---------------------------------------------------------------------------
# Dropout (dropout-inl.h:256) — scaled inverted dropout, identity at eval.
# ---------------------------------------------------------------------------

def _dropout_apply(attrs, inputs, is_train, rng):
    p = float(attrs.get('p', 0.5))
    data = inputs[0]
    if not is_train or p <= 0.0:
        return [data], {}
    keep = 1.0 - p
    mask = jax.random.bernoulli(rng, keep, data.shape)
    return [jnp.where(mask, data / keep, 0.0).astype(data.dtype)], {}


register('Dropout', _dropout_apply,
         input_names=lambda attrs: ['data'],
         num_outputs=lambda attrs: 1,
         takes_rng=True,
         attr_defaults={'p': 0.5}, hint='dropout')


# ---------------------------------------------------------------------------
# Concat / SliceChannel (concat-inl.h, slice_channel-inl.h)
# ---------------------------------------------------------------------------

def _concat_apply(attrs, inputs, is_train, rng):
    dim = int(attrs.get('dim', 1))
    return [jnp.concatenate(list(inputs), axis=dim)], {}


register('Concat', _concat_apply,
         input_names=lambda attrs: ['arg%d' % i
                                    for i in range(int(attrs.get('num_args', 1)))],
         num_outputs=lambda attrs: 1,
         attr_defaults={'num_args': 1, 'dim': 1}, hint='concat')
alias('concat', 'Concat')


def _slice_channel_apply(attrs, inputs, is_train, rng):
    num = int(attrs.get('num_outputs', 1))
    axis = int(attrs.get('axis', 1))
    squeeze = bool(attrs.get('squeeze_axis', False))
    parts = jnp.split(inputs[0], num, axis=axis)
    if squeeze:
        parts = [jnp.squeeze(p, axis=axis) for p in parts]
    return parts, {}


register('SliceChannel', _slice_channel_apply,
         input_names=lambda attrs: ['data'],
         num_outputs=lambda attrs: int(attrs.get('num_outputs', 1)),
         attr_defaults={'num_outputs': 1, 'axis': 1, 'squeeze_axis': False},
         hint='slicechannel')
alias('split', 'SliceChannel')


# ---------------------------------------------------------------------------
# Embedding (indexing_op.h) — gather on the MXU-friendly one-hot path is
# left to XLA; jnp.take emits a dynamic-gather.
# ---------------------------------------------------------------------------

def _embedding_apply(attrs, inputs, is_train, rng):
    data, weight = inputs
    return [jnp.take(weight, data.astype(jnp.int32), axis=0)], {}


def _embedding_complete(attrs, in_shapes):
    _complete(in_shapes, 1, (int(attrs['input_dim']), int(attrs['output_dim'])))
    return in_shapes


register('Embedding', _embedding_apply,
         input_names=lambda attrs: ['data', 'weight'],
         num_outputs=lambda attrs: 1,
         complete_shapes=_embedding_complete, keep_dtype=('data',),
         attr_defaults={'dtype': 'float32'}, hint='embedding')


# ---------------------------------------------------------------------------
# UpSampling / Crop (upsampling-inl.h:318, crop-inl.h)
# ---------------------------------------------------------------------------

def _upsampling_apply(attrs, inputs, is_train, rng):
    scale = int(attrs.get('scale', 2))
    sample_type = attrs.get('sample_type', 'nearest')
    data = inputs[0]
    if sample_type == 'nearest':
        out = jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
    else:
        n, c, h, w = data.shape
        out = jax.image.resize(data, (n, c, h * scale, w * scale), 'bilinear')
    return [out], {}


register('UpSampling', _upsampling_apply,
         input_names=lambda attrs: ['arg%d' % i
                                    for i in range(int(attrs.get('num_args', 1)))],
         num_outputs=lambda attrs: 1,
         attr_defaults={'num_args': 1, 'scale': 2, 'sample_type': 'nearest',
                        'num_filter': 0}, hint='upsampling')


def _crop_apply(attrs, inputs, is_train, rng):
    data = inputs[0]
    offset = _tup(attrs.get('offset'), 2, default=0)
    center_crop = bool(attrs.get('center_crop', False))
    if len(inputs) == 2:
        th, tw = inputs[1].shape[2], inputs[1].shape[3]
    else:
        th, tw = _tup(attrs['h_w'], 2)
    h, w = data.shape[2], data.shape[3]
    if center_crop:
        y0, x0 = (h - th) // 2, (w - tw) // 2
    else:
        y0, x0 = offset
    return [data[:, :, y0:y0 + th, x0:x0 + tw]], {}


register('Crop', _crop_apply,
         input_names=lambda attrs: (['data', 'crop_like']
                                    if int(attrs.get('num_args', 1)) == 2
                                    else ['data']),
         num_outputs=lambda attrs: 1,
         attr_defaults={'num_args': 1, 'offset': (0, 0), 'h_w': (0, 0),
                        'center_crop': False}, hint='crop')


# ---------------------------------------------------------------------------
# Sequence ops (sequence_last/mask/reverse-inl.h).  Layout (T, N, ...)
# like the reference.
# ---------------------------------------------------------------------------

def _seq_len_or_full(inputs, attrs, T, N):
    if bool(attrs.get('use_sequence_length', False)) and len(inputs) > 1:
        return inputs[1].astype(jnp.int32)
    return jnp.full((N,), T, jnp.int32)


def _sequence_last_apply(attrs, inputs, is_train, rng):
    data = inputs[0]
    T, N = data.shape[0], data.shape[1]
    lengths = _seq_len_or_full(inputs, attrs, T, N)
    idx = jnp.clip(lengths - 1, 0, T - 1)
    out = jnp.take_along_axis(
        data, idx.reshape((1, N) + (1,) * (data.ndim - 2)), axis=0)[0]
    return [out], {}


def _sequence_mask_apply(attrs, inputs, is_train, rng):
    data = inputs[0]
    value = float(attrs.get('value', 0.0))
    T, N = data.shape[0], data.shape[1]
    lengths = _seq_len_or_full(inputs, attrs, T, N)
    mask = (jnp.arange(T)[:, None] < lengths[None, :])
    mask = mask.reshape((T, N) + (1,) * (data.ndim - 2))
    return [jnp.where(mask, data, value).astype(data.dtype)], {}


def _sequence_reverse_apply(attrs, inputs, is_train, rng):
    data = inputs[0]
    T, N = data.shape[0], data.shape[1]
    lengths = _seq_len_or_full(inputs, attrs, T, N)
    t = jnp.arange(T)[:, None]
    src = jnp.where(t < lengths[None, :], lengths[None, :] - 1 - t, t)
    out = jnp.take_along_axis(
        data, src.reshape((T, N) + (1,) * (data.ndim - 2)), axis=0)
    return [out], {}


for _nm, _fn in [('SequenceLast', _sequence_last_apply),
                 ('SequenceMask', _sequence_mask_apply),
                 ('SequenceReverse', _sequence_reverse_apply)]:
    register(_nm, _fn,
             input_names=lambda attrs: (
                 ['data', 'sequence_length']
                 if attrs.get('use_sequence_length', False) else ['data']),
             num_outputs=lambda attrs: 1,
             attr_defaults={'use_sequence_length': False, 'value': 0.0},
             hint=_nm.lower())


# ---------------------------------------------------------------------------
# Fused attention (beyond the reference op set: the symbol-level door
# to the Pallas flash-attention kernel, so Module users get the fused
# path without writing JAX; parallel/ring.py adds the sequence-parallel
# form for mesh code)
# ---------------------------------------------------------------------------


def _flash_attention_apply(attrs, inputs, is_train, rng):
    from .pallas_attention import flash_attention
    q, k, v = inputs
    causal = bool(attrs.get('causal', False))
    scale = attrs.get('scale')
    # sequence-parallel tracing scope (parallel/sp.py): this node is
    # executing inside shard_map with the sequence dim sharded — run
    # ring attention over the mesh axis instead of a local kernel.
    from ..parallel.sp import current_sp_axis, current_sp_mode
    axis = current_sp_axis()
    if k.shape[1] != q.shape[1] or v.shape[-1] != q.shape[-1]:
        # grouped queries: fewer key-value heads than query heads; or
        # values of another size than the keys (latent attention)
        if axis is not None:
            raise NotImplementedError('FlashAttention: grouped-query or '
                                      'latent attention under sequence '
                                      'parallelism')
        from .pallas_attention import gqa_attention
        return [gqa_attention(q, k, v, causal=causal,
                              scale=float(scale) if scale is not None
                              else None)], {}
    if axis is not None:
        from ..parallel.ring import ring_attention, full_attention
        if scale is not None:
            # the sharded kernels bake 1/sqrt(D); fold custom scale in
            q = q * (float(scale) * (q.shape[-1] ** 0.5))
        if current_sp_mode() == 'ulysses':
            # all-to-all: seq-sharded -> head-sharded, local full
            # attention, swap back (DeepSpeed-Ulysses recipe)
            def s2h(x):
                return jax.lax.all_to_all(x, axis, split_axis=1,
                                          concat_axis=2, tiled=True)
            def h2s(x):
                return jax.lax.all_to_all(x, axis, split_axis=2,
                                          concat_axis=1, tiled=True)
            oh = full_attention(s2h(q), s2h(k), s2h(v), causal=causal)
            return [h2s(oh)], {}
        return [ring_attention(q, k, v, axis, causal=causal)], {}
    out = flash_attention(q, k, v, causal=causal,
                          scale=float(scale) if scale is not None
                          else None)
    return [out], {}


def _flash_attention_complete(attrs, in_shapes):
    q = in_shapes[0]
    if q is not None:
        for i in (1, 2):
            if in_shapes[i] is None:
                in_shapes[i] = tuple(q)
    return in_shapes


register('FlashAttention', _flash_attention_apply,
         input_names=lambda attrs: ['query', 'key', 'value'],
         num_outputs=lambda attrs: 1,
         complete_shapes=_flash_attention_complete,
         attr_defaults={'causal': False, 'scale': None},
         hint='attention')
