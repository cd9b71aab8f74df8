"""The step compiler — a sequenced, knob-gated graph-rewrite pass
pipeline run on every symbol entering ``make_fit_step``, the
``Executor``'s one-program jit paths, and (through them) ``Predictor``.

TVM (PAPERS.md 1802.04799) showed that a small set of graph-level
rewrites run *before* codegen — operator fusion, compute folding,
layout planning — is where the cheap 20-40% lives; the Julia-to-TPU
work (1810.09868) showed the same on XLA specifically: hand the
partitioner bigger fused regions and it does the rest.  This module
grew from two ad-hoc rewrites wired by a hardcoded call into a real
:class:`PassManager`:

==================  ==========  =============================================
pass                level       rewrite
==================  ==========  =============================================
``constant_fold``   safe        pre-evaluate constant subgraphs at bind time
``dead_branch``     safe        elide identity nodes; drop unconsumed
                                BatchNorm mean/var heads
``conv_bn_fold``    aggressive  Convolution->BatchNorm folded into the conv
                                weights — at inference always, in TRAINING
                                when the BN runs on moving stats
                                (use_global_stats)
``bn_relu_conv``    aggressive  BN->relu->conv collapsed into the Pallas
                                fused-prologue kernels (the PR-2 rewrite)
``bn_relu``         aggressive  leftover BN->relu chains onto the fused
                                BN-ReLU kernel (ops/pallas_fused)
``epilogue``        safe        bias-add/relu/clip chains following
                                Conv/FC/dot collapsed into the producer
                                (bit-exact replay; the fused_dot_epilogue
                                kernel lowering arms under aggressive
                                when Mosaic allows)
``nhwc_regions``    aggressive  grow channels-last layout regions across
                                fused ops instead of bouncing transposes
==================  ==========  =============================================

``MXTPU_FUSE=off|safe|aggressive`` selects the pass set (``off`` means
byte-identical to the unfused program — the pipeline returns the input
symbol object untouched); unset means ``off``.
``MXTPU_FUSE_SKIP=name,name`` disables individual passes.  Every pass
reports ``fuse.pass.<name>.{rewrites,nodes_removed}`` through perfwatch
(:func:`perfwatch.note_fuse`), and ``tools/check_fusion.py`` gates the
pipeline hermetically: per-pass oracle parity (safe passes bit-for-bit,
folding passes rtol<=1e-5) plus a registered-executable
``cost_analysis`` bytes/flops drop under ``aggressive``.

Original module docstring (the PR-2 rewrite, now the ``bn_relu_conv``
pass):

``fuse_bn_relu_conv`` rewrites the ResNet-v2 hot pattern

    BatchNorm -> Activation(relu) -> Convolution (1x1 s1/s2, 3x3 s1/s2)

into ``_bn_relu_conv`` nodes whose apply computes the batch statistics
(one reduction pass) and then runs a Pallas kernel with the
normalize+relu folded into the conv's input stream — the activation
crosses HBM once instead of three times.  1x1 convs lower to the fused
scale-bias matmul (``ops/pallas_fused.py``); 3x3 convs to the fused
conv kernel (``ops/pallas_conv.py``).  This is the framework-level
counterpart of the reference's cuDNN fused-epilogue kernels
(``src/operator/cudnn_convolution-inl.h:638``); XLA cannot express
reduction-feeding-prologue fusion around a convolution itself.

Multi-consumer chains fuse too: when EVERY consumer of the relu is a
fusable conv (ResNet's unit-entry BN shared by the main path and the
projection shortcut), each conv gets its own fused node — the batch
statistics are identical XLA subexpressions (CSE'd to one reduction)
and the normalized activation never materializes.  If any consumer is
not a fusable conv the chain is left alone (the activation would
materialize for that consumer anyway, making fusion traffic-neutral).

Enabled for Module.fit / make_fit_step via ``MXTPU_FUSE=aggressive``.
The rewrite preserves parameter names,
aux state and observable numerics (tests/test_fuse_bn_conv.py asserts
fwd+bwd equality for every shape class).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import config
from .symbol import Symbol, Node

__all__ = ['fuse_bn_relu_conv', 'fuse_bn_relu_conv1x1',
           'fold_conv_bn_inference', 'fold_conv_bn', 'fold_constants',
           'prune_dead_branches', 'fuse_bn_relu', 'fuse_epilogues',
           'FusePass', 'PassManager', 'default_passes',
           'default_manager', 'fuse_mode', 'apply_fuse_passes',
           'last_run_stats']


def _tup_or(v, default):
    if v is None or v == ():
        return default
    if isinstance(v, int):
        return (v, v)
    return tuple(int(x) for x in v)


def _bn_scale_bias(attrs, data, gamma, beta, mov_mean, mov_var,
                   is_train, axes=(0, 2, 3)):
    """Stats step folded to per-channel (scale, bias).  Delegates the
    statistics math to ops/nn.py ``batch_norm_stats`` — ONE copy, so
    fused/unfused numerics cannot drift.  ``axes`` are the reduction
    axes (default NCHW; NHWC regions pass (0, 1, 2))."""
    from .ops.nn import batch_norm_stats
    eps = float(attrs.get('eps', 1e-3))
    momentum = float(attrs.get('momentum', 0.9))
    fix_gamma = bool(attrs.get('fix_gamma', True))
    use_global = bool(attrs.get('use_global_stats', False))
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    mean, var, aux_updates = batch_norm_stats(
        data, mov_mean, mov_var, axes, momentum,
        is_train and not use_global)
    scale = (g * jax.lax.rsqrt(var + eps)).astype(data.dtype)
    bias = (beta - mean * scale).astype(data.dtype)
    return scale, bias, aux_updates


def _register_fused_op():
    from .ops.registry import register, _REGISTRY
    if '_bn_relu_conv' in _REGISTRY:
        return
    from .ops.pallas_fused import fused_scale_bias_dot
    from .ops.pallas_conv import fused_scale_bias_conv3x3

    def apply_fn(attrs, inputs, is_train, rng):
        data, gamma, beta, weight = inputs[:4]
        in_nhwc = attrs.get('in_layout', 'NCHW') == 'NHWC'
        out_nhwc = attrs.get('out_layout', 'NCHW') == 'NHWC'
        # BN statistics reduce over (N, H, W) — the non-channel axes
        # of whichever layout the data arrives in
        scale, bias, aux_updates = _bn_scale_bias(
            attrs, data, gamma, beta, inputs[4], inputs[5], is_train,
            axes=(0, 1, 2) if in_nhwc else (0, 2, 3))
        kernel = _tup_or(attrs.get('kernel'), (1, 1))
        stride_hw = _tup_or(attrs.get('stride'), (1, 1))
        # the rewrite gate only emits these classes; fail fast on a
        # hand-built node outside the contract instead of silently
        # running wrong numerics
        if kernel not in ((1, 1), (3, 3)) or \
                stride_hw not in ((1, 1), (2, 2)):
            raise ValueError('_bn_relu_conv supports kernel 1x1/3x3 '
                             'with square stride 1/2, got kernel=%s '
                             'stride=%s' % (kernel, stride_hw))
        stride = stride_hw[0]
        x = data if in_nhwc else jnp.transpose(data, (0, 2, 3, 1))
        n, c = x.shape[0], x.shape[3]
        if kernel == (1, 1):
            if stride > 1:
                x = x[:, ::stride, ::stride, :]
            oh, ow = x.shape[1], x.shape[2]
            x2d = x.reshape(-1, c)
            w2d = weight.reshape(weight.shape[0], c).T   # (C, Nf)
            y2d = fused_scale_bias_dot(x2d, w2d.astype(data.dtype),
                                       scale, bias, relu=True)
            y = y2d.reshape(n, oh, ow, -1)
        else:
            whwio = jnp.transpose(weight, (2, 3, 1, 0))     # HWIO
            y = fused_scale_bias_conv3x3(
                x, whwio.astype(data.dtype), scale, bias,
                stride=stride, relu=True)
        if not out_nhwc:
            y = jnp.transpose(y, (0, 3, 1, 2))
        return [y], aux_updates

    def complete(attrs, in_shapes):
        d = in_shapes[0]
        if d is not None:
            c = d[3] if attrs.get('in_layout', 'NCHW') == 'NHWC' \
                else d[1]
            for i in (1, 2):
                if in_shapes[i] is None:
                    in_shapes[i] = (c,)
            if in_shapes[3] is None:
                k = _tup_or(attrs.get('kernel'), (1, 1))
                in_shapes[3] = (int(attrs['num_filter']), c) + k
        return in_shapes

    register('_bn_relu_conv', apply_fn,
             input_names=lambda a: ['data', 'gamma', 'beta', 'weight'],
             aux_names=lambda a: ['moving_mean', 'moving_var'],
             num_outputs=lambda a: 1,
             complete_shapes=complete,
             attr_defaults={'eps': 1e-3, 'momentum': 0.9,
                            'fix_gamma': True,
                            'use_global_stats': False,
                            'num_filter': 0, 'kernel': (1, 1),
                            'stride': (1, 1)},
             hint='bn_relu_conv')


def _is_fusable_conv(node: Node) -> bool:
    if node.op != 'Convolution' or not node.attrs.get('no_bias', False):
        return False
    a = node.attrs
    if a.get('pad_hi') or int(a.get('num_group', 1)) != 1:
        return False
    if _tup_or(a.get('dilate'), (1, 1)) != (1, 1):
        return False    # the fused kernels compute dilation-1 only
    kernel = tuple(a.get('kernel', ()))
    stride = _tup_or(a.get('stride'), (1, 1))
    pad = _tup_or(a.get('pad'), (0, 0))
    if stride not in ((1, 1), (2, 2)):
        return False
    if kernel == (1, 1):
        return pad == (0, 0)
    if kernel == (3, 3):
        return pad == (1, 1)
    return False


def _rewrite(sym: Symbol, try_fuse) -> Symbol:
    """Shared graph-rewrite scaffolding: walk topo order, let
    ``try_fuse(node, consumer_list, mapped_entry)`` return a
    replacement Node (or None to copy verbatim), rebuild the Symbol."""
    nodes = sym.topo_nodes()
    consumers = {}

    def add_consumer(entry, node):
        consumers.setdefault((id(entry[0]), entry[1]), []).append(node)

    for n in nodes:
        for inp in n.inputs:
            add_consumer(inp, n)
    for entry in sym._outputs:
        add_consumer(entry, None)   # graph output counts as a consumer

    def consumer_list(node, idx=0):
        return consumers.get((id(node), idx), [])

    mapping = {}

    def mapped_entry(entry):
        node, idx = entry
        return (mapping[id(node)], idx)

    for n in nodes:
        if n.is_variable:
            mapping[id(n)] = n
            continue
        fused = try_fuse(n, consumer_list, mapped_entry)
        if fused is None:
            fused = Node(n.op, n.name, n.attrs,
                         [mapped_entry(e) for e in n.inputs])
            fused._extra_attr = n._extra_attr
        mapping[id(n)] = fused

    return Symbol([mapped_entry(e) for e in sym._outputs])


def _rewrite_counted(sym: Symbol, try_fuse):
    """:func:`_rewrite` with a rewrite count — returns ``(sym, n)``
    where ``n`` is how many nodes ``try_fuse`` replaced.  ``n == 0``
    hands back the ORIGINAL symbol object (no graph churn, byte-
    identical downstream program)."""
    cell = [0]

    def counting(n, consumer_list, mapped_entry):
        fused = try_fuse(n, consumer_list, mapped_entry)
        if fused is not None:
            cell[0] += 1
        return fused

    out = _rewrite(sym, counting)
    if cell[0] == 0:
        return sym, 0
    return out, cell[0]


# elementwise ops that pass NHWC data through untouched (same-shape
# two-operand arithmetic; anything axis-sensitive is a region boundary)
_LAYOUT_FLEX = {'_plus', 'elemwise_add', '_grad_add', '_minus', '_mul'}
# single-operand elementwise ops a channels-last region grows across —
# the generalization that keeps post-residual relu/clip chains (and the
# epilogue pass's leftovers) from bouncing a transpose pair per node.
# 'Activation' covers relu/sigmoid/tanh/softrelu: all elementwise.
_LAYOUT_FLEX_UNARY = {'Activation', 'clip'}


def _layout_transpose_name(src_name, out_idx, want):
    """Name for a layout-conversion transpose node.  The output index
    disambiguates: two outputs of one multi-output node must not
    produce identically named transposes (monitor taps and graph dumps
    key by node name)."""
    suffix = '' if out_idx == 0 else '_out%d' % out_idx
    return '%s%s_to_%s' % (src_name, suffix, want.lower())


def _nhwc_regions(sym: Symbol) -> Symbol:
    """Keep fused chains channels-last end-to-end.

    Every ``_bn_relu_conv`` produces NHWC; elementwise ops between them
    (ResNet's residual adds, plus the unary relu/clip chains in
    ``_LAYOUT_FLEX_UNARY``) operate on NHWC data unchanged; an explicit
    ``transpose`` node appears only where an NHWC tensor meets a
    layout-sensitive consumer (or a graph output).  Without this pass
    each fused node is sandwiched in its own NCHW<->NHWC transposes —
    and since Pallas custom calls have FIXED operand layouts, XLA
    cannot always absorb those the way it can for native ops, risking a
    materialized activation copy per kernel.
    """
    return _nhwc_regions_counted(sym)[0]


def _nhwc_regions_counted(sym: Symbol):
    """(symbol, region nodes) — the :func:`_nhwc_regions` rewrite with
    the grown-region size reported as the pass's rewrite count."""
    nodes = sym.topo_nodes()
    if not any(n.op == '_bn_relu_conv' for n in nodes
               if not n.is_variable):
        # no NHWC producers: nothing to grow, keep the original graph
        return sym, 0
    grown = [0]
    mapping = {}     # id(old node) -> new node
    layout = {}      # (id(new node), idx) -> 'NCHW' | 'NHWC'
    to_nchw_cache = {}
    to_nhwc_cache = {}

    def mapped(entry):
        return (mapping[id(entry[0])], entry[1])

    def as_layout(entry, want):
        """Entry in the requested layout, inserting (and sharing) a
        transpose node when needed."""
        new_entry = mapped(entry)
        have = layout.get((id(new_entry[0]), new_entry[1]), 'NCHW')
        if have == want:
            return new_entry
        cache = to_nhwc_cache if want == 'NHWC' else to_nchw_cache
        key = (id(new_entry[0]), new_entry[1])
        t = cache.get(key)
        if t is None:
            axes = (0, 2, 3, 1) if want == 'NHWC' else (0, 3, 1, 2)
            t = Node('transpose',
                     _layout_transpose_name(entry[0].name, new_entry[1],
                                            want),
                     {'axes': axes}, [new_entry])
            cache[key] = t
        return (t, 0)

    for n in nodes:
        if n.is_variable:
            mapping[id(n)] = n
            continue
        if n.op == '_bn_relu_conv':
            in_entry = mapped(n.inputs[0])
            in_lay = layout.get((id(in_entry[0]), in_entry[1]), 'NCHW')
            attrs = dict(n.attrs)
            attrs['in_layout'] = in_lay
            attrs['out_layout'] = 'NHWC'
            new = Node(n.op, n.name, attrs,
                       [in_entry] + [mapped(e) for e in n.inputs[1:]])
            new._extra_attr = n._extra_attr
            layout[(id(new), 0)] = 'NHWC'
            grown[0] += 1
        elif n.op in _LAYOUT_FLEX and len(n.inputs) == 2 and any(
                layout.get((id(mapped(e)[0]), mapped(e)[1]),
                           'NCHW') == 'NHWC' for e in n.inputs):
            # grow the region: both operands to NHWC, output NHWC
            new = Node(n.op, n.name, n.attrs,
                       [as_layout(e, 'NHWC') for e in n.inputs])
            new._extra_attr = n._extra_attr
            layout[(id(new), 0)] = 'NHWC'
            grown[0] += 1
        elif n.op in _LAYOUT_FLEX_UNARY and len(n.inputs) == 1 and \
                n.num_outputs() == 1 and \
                layout.get((id(mapped(n.inputs[0])[0]),
                            mapped(n.inputs[0])[1]), 'NCHW') == 'NHWC':
            # grow through single-operand elementwise ops: the data
            # passes through in whatever layout it arrived
            new = Node(n.op, n.name, n.attrs,
                       [mapped(n.inputs[0])])
            new._extra_attr = n._extra_attr
            layout[(id(new), 0)] = 'NHWC'
            grown[0] += 1
        else:
            new = Node(n.op, n.name, n.attrs,
                       [as_layout(e, 'NCHW') for e in n.inputs])
            new._extra_attr = n._extra_attr
        mapping[id(n)] = new

    outs = [as_layout(e, 'NCHW') for e in sym._outputs]
    return Symbol(outs), grown[0]


def _try_fuse_bn_relu_conv(n, consumer_list, mapped_entry):
    """The BN->relu->conv matcher (shared by the public one-shot
    rewrite and the pipeline's ``bn_relu_conv`` pass)."""
    if _is_fusable_conv(n):
        act, _ = n.inputs[0]
        if (not act.is_variable and act.op == 'Activation'
                and act.attrs.get('act_type') == 'relu'
                and all(c is not None and _is_fusable_conv(c)
                        for c in consumer_list(act))):
            bn, _ = act.inputs[0]
            if (not bn.is_variable and bn.op == 'BatchNorm'
                    and len(consumer_list(bn)) == 1
                    and not bn.attrs.get('output_mean_var', False)):
                attrs = {
                    'eps': bn.attrs.get('eps', 1e-3),
                    'momentum': bn.attrs.get('momentum', 0.9),
                    'fix_gamma': bn.attrs.get('fix_gamma', True),
                    'use_global_stats':
                        bn.attrs.get('use_global_stats', False),
                    'num_filter': n.attrs['num_filter'],
                    'kernel': tuple(n.attrs.get('kernel', (1, 1))),
                    'stride': _tup_or(n.attrs.get('stride'), (1, 1)),
                }
                # bn inputs: data gamma beta + aux mean/var;
                # conv inputs: act weight
                ins = [mapped_entry(bn.inputs[0]),
                       mapped_entry(bn.inputs[1]),
                       mapped_entry(bn.inputs[2]),
                       mapped_entry(n.inputs[1]),
                       mapped_entry(bn.inputs[3]),
                       mapped_entry(bn.inputs[4])]
                fused = Node('_bn_relu_conv', n.name + '_fused',
                             attrs, ins)
                fused._extra_attr = dict(n._extra_attr)
                return fused
    return None


def fuse_bn_relu_conv(sym: Symbol) -> Symbol:
    """Return a copy of ``sym`` with every BN -> relu -> conv chain
    whose relu feeds ONLY fusable convs collapsed into per-conv
    ``_bn_relu_conv`` nodes, then kept channels-last end-to-end by
    :func:`_nhwc_regions`."""
    _register_fused_op()
    return _nhwc_regions(_rewrite(sym, _try_fuse_bn_relu_conv))


# round-3 name — the pass now also covers 3x3 and strided convs
fuse_bn_relu_conv1x1 = fuse_bn_relu_conv


def _register_folded_op():
    from .ops.registry import register, _REGISTRY
    if '_conv_bn_folded' in _REGISTRY:
        return
    from .ops.nn import _conv_apply

    def apply_fn(attrs, inputs, is_train, rng):
        no_bias = bool(attrs.get('no_bias', True))
        if no_bias:
            data, weight, gamma, beta, mov_mean, mov_var = inputs
            conv_bias = None
        else:
            data, weight, conv_bias, gamma, beta, mov_mean, \
                mov_var = inputs
        eps = float(attrs.get('eps', 1e-3))
        fix_gamma = bool(attrs.get('fix_gamma', True))
        g = jnp.ones_like(gamma) if fix_gamma else gamma
        mean = jax.lax.stop_gradient(mov_mean)
        var = jax.lax.stop_gradient(mov_var)
        inv = g * jax.lax.rsqrt(var + eps)
        scale = inv.astype(weight.dtype)
        # bn(conv + c) = conv(x, w*s) + (beta + (c - mean) * s)
        shift = mean if conv_bias is None else mean - conv_bias
        bias = (beta - shift * inv).astype(weight.dtype)
        # fold per-output-channel scale into the weights (O(params),
        # trivial next to the saved activation pass), run ONE conv
        wshape = (weight.shape[0],) + (1,) * (weight.ndim - 1)
        conv_attrs = {k: v for k, v in attrs.items()
                      if k not in ('eps', 'momentum', 'fix_gamma',
                                   'use_global_stats')}
        conv_attrs['no_bias'] = True
        outs, _ = _conv_apply(conv_attrs,
                              [data, weight * scale.reshape(wshape)],
                              is_train, rng)
        y = outs[0] + bias.reshape((1, -1) + (1,) * (data.ndim - 2))
        return [y], {}

    def complete(attrs, in_shapes):
        d = in_shapes[0]
        nf = int(attrs.get('num_filter', 0))
        if d is not None and in_shapes[1] is None and nf:
            k = _tup_or(attrs.get('kernel'), (1, 1))
            in_shapes[1] = (nf, d[1]) + k
        if in_shapes[1] is not None:
            nf = in_shapes[1][0]
            for i in range(2, len(in_shapes)):
                if in_shapes[i] is None:
                    in_shapes[i] = (nf,)
        return in_shapes

    register('_conv_bn_folded', apply_fn,
             input_names=lambda a: (
                 ['data', 'weight', 'gamma', 'beta']
                 if bool(a.get('no_bias', True))
                 else ['data', 'weight', 'bias', 'gamma', 'beta']),
             aux_names=lambda a: ['moving_mean', 'moving_var'],
             aux_shape=lambda a, ins: [(int(a['num_filter']),)] * 2,
             num_outputs=lambda a: 1,
             complete_shapes=complete,
             attr_defaults={'eps': 1e-3, 'fix_gamma': True,
                            'no_bias': True,
                            'num_filter': 0, 'kernel': (1, 1)},
             hint='conv_bn_folded')


def fold_conv_bn(sym: Symbol, is_train=False, mode='safe'):
    """Collapse Convolution -> BatchNorm into one conv with BN folded
    into the weights — the post-norm pattern (inception/classic-resnet
    stems: conv->bn->relu) that :func:`fuse_bn_relu_conv` cannot touch.
    With moving statistics the fold is exact:
    ``bn(conv(x, w)) = conv(x, w*s) + b``.  The conv output never
    materializes, halving that chain's activation traffic.

    At inference every such chain folds.  In TRAINING the fold applies
    only when the BN runs on moving statistics anyway
    (``use_global_stats=True`` — fine-tuning with frozen stats, the
    common transfer-learning configuration): the folded expression is
    differentiable in weight/gamma/beta, so gradients match the
    unfused graph to float tolerance.  A BN with live batch statistics
    falls through untouched (the stats depend on the conv output).
    Returns ``(symbol, rewrites)``."""
    _register_folded_op()

    def try_fuse(n, consumer_list, mapped_entry):
        if (n.op == 'BatchNorm'
                and not n.attrs.get('output_mean_var', False)):
            if is_train and not n.attrs.get('use_global_stats', False):
                return None     # live batch statistics: fold invalid
            conv, cidx = n.inputs[0]
            if (not conv.is_variable and conv.op == 'Convolution'
                    and int(conv.attrs.get('num_group', 1)) == 1
                    and len(consumer_list(conv)) == 1):
                no_bias = bool(conv.attrs.get('no_bias', False))
                attrs = dict(conv.attrs)
                attrs['no_bias'] = no_bias
                attrs['eps'] = n.attrs.get('eps', 1e-3)
                attrs['fix_gamma'] = n.attrs.get('fix_gamma', True)
                ins = [mapped_entry(conv.inputs[0]),
                       mapped_entry(conv.inputs[1])]
                if not no_bias:
                    ins.append(mapped_entry(conv.inputs[2]))
                ins += [mapped_entry(n.inputs[1]),
                        mapped_entry(n.inputs[2]),
                        mapped_entry(n.inputs[3]),
                        mapped_entry(n.inputs[4])]
                fused = Node('_conv_bn_folded', n.name + '_folded',
                             attrs, ins)
                fused._extra_attr = dict(n._extra_attr)
                return fused
        return None

    return _rewrite_counted(sym, try_fuse)


def fold_conv_bn_inference(sym: Symbol) -> Symbol:
    """Compat wrapper: the inference-mode :func:`fold_conv_bn`."""
    return fold_conv_bn(sym, is_train=False)[0]


# ---------------------------------------------------------------------------
# constant folding — pre-evaluate constant subgraphs at bind time
# ---------------------------------------------------------------------------

# ops that generate a constant from attrs alone (the fold frontier);
# any rng-free, aux-free node all of whose inputs are constant extends it
_CONST_LEAF_OPS = ('_zeros', '_ones', '_full', '_arange')
# never embed constants past this size: XLA inlines them into the
# program, and a huge literal bloats the executable for a fold XLA
# would have done itself
_CONST_FOLD_MAX_ELEMS = 65536


def _register_const_op():
    from .ops.registry import register, _REGISTRY
    if '_graph_constant' in _REGISTRY:
        return

    def apply_fn(attrs, inputs, is_train, rng):
        # value rides attrs in nested-list form (JSON-able, so the
        # compile-cache fingerprint of a folded symbol stays stable
        # across processes); rebuild the exact array
        arr = np.array(attrs['value'], dtype=attrs['dtype'])
        return [jnp.asarray(arr.reshape(tuple(attrs['shape'])))], {}

    register('_graph_constant', apply_fn,
             input_names=lambda a: [],
             num_outputs=lambda a: 1,
             hint='graph_constant')


def _const_attrs(value):
    """JSON-able attr form of a folded numpy constant."""
    v = np.asarray(value)
    return {'value': v.tolist(), 'dtype': str(v.dtype),
            'shape': tuple(v.shape)}


def fold_constants(sym: Symbol, is_train=False, mode='safe'):
    """Pre-evaluate constant subgraphs (rooted at ``_zeros``/``_ones``/
    ``_full``/``_arange``) at pass time and splice the results in as
    ``_graph_constant`` nodes — the TVM-style compute-folding pass.
    Conservative by construction: only rng-free, aux-free,
    exception-free nodes whose inputs are all constant fold, and
    results above ``_CONST_FOLD_MAX_ELEMS`` stay symbolic.  Returns
    ``(symbol, constants materialized)``."""
    _register_const_op()
    nodes = sym.topo_nodes()
    vals = {}           # id(node) -> list of np outputs

    for node in nodes:
        if node.is_variable:
            continue
        if node.op == '_graph_constant':
            vals[id(node)] = [np.array(
                node.attrs['value'],
                dtype=node.attrs['dtype']).reshape(
                    tuple(node.attrs['shape']))]
            continue
        op = node.opdef()
        if op.takes_rng or op.aux_names(node.attrs):
            continue
        if node.inputs:
            if not all(id(s) in vals for s, _ in node.inputs):
                continue
            ins = [jnp.asarray(vals[id(s)][j]) for s, j in node.inputs]
        elif node.op in _CONST_LEAF_OPS:
            ins = []
        else:
            continue
        try:
            outs, aux = op.apply(node.attrs, ins, False, None)
        except Exception:
            continue
        if aux:
            continue
        outs = [np.asarray(o) for o in outs]
        if any(o.size > _CONST_FOLD_MAX_ELEMS for o in outs):
            continue
        vals[id(node)] = outs

    if not vals or all(n.op == '_graph_constant' for n in nodes
                       if id(n) in vals):
        return sym, 0

    new_nodes = {}
    const_nodes = {}    # (id(old node), out idx) -> materialized Node
    count = [0]

    def const_entry(node, idx):
        key = (id(node), idx)
        c = const_nodes.get(key)
        if c is None:
            name = node.name if idx == 0 else \
                '%s_out%d' % (node.name, idx)
            c = Node('_graph_constant', name,
                     _const_attrs(vals[id(node)][idx]), [])
            c._extra_attr = dict(node._extra_attr)
            const_nodes[key] = c
            count[0] += 1
        return (c, 0)

    def mapped(entry):
        s, j = entry
        if not s.is_variable and id(s) in vals and \
                s.op != '_graph_constant':
            return const_entry(s, j)
        return (new_nodes[id(s)], j)

    for node in nodes:
        if node.is_variable:
            new_nodes[id(node)] = node
            continue
        if id(node) in vals and node.op != '_graph_constant':
            continue    # folded away; consumers materialize lazily
        nn = Node(node.op, node.name, node.attrs,
                  [mapped(e) for e in node.inputs])
        nn._extra_attr = node._extra_attr
        new_nodes[id(node)] = nn

    outputs = [mapped(e) for e in sym._outputs]
    if count[0] == 0:
        return sym, 0
    return Symbol(outputs), count[0]


# ---------------------------------------------------------------------------
# dead-branch elimination — identity elision + unconsumed aux heads
# ---------------------------------------------------------------------------

def prune_dead_branches(sym: Symbol, is_train=False, mode='safe'):
    """Two structure-preserving prunes: (1) ``identity`` nodes are
    elided (consumers read the input entry directly) unless they carry
    placement attrs or name a graph output; (2) a BatchNorm emitting
    ``output_mean_var`` heads that NOTHING consumes is rebuilt with
    ``output_mean_var=False``, so the mean/rstd outputs are never
    staged out of the compiled program.  Returns
    ``(symbol, rewrites)``."""
    nodes = sym.topo_nodes()
    consumers = {}
    for n in nodes:
        for s, j in n.inputs:
            consumers.setdefault((id(s), j), []).append(n)
    for s, j in sym._outputs:
        consumers.setdefault((id(s), j), []).append(None)

    emap = {}
    count = [0]

    def mapped(entry):
        s, j = entry
        if s.is_variable:
            return (s, j)
        return emap[(id(s), j)]

    changed = False
    for node in nodes:
        if node.is_variable:
            continue
        if node.op == 'identity' and not node._extra_attr and \
                None not in consumers.get((id(node), 0), []):
            emap[(id(node), 0)] = mapped(node.inputs[0])
            count[0] += 1
            changed = True
            continue
        attrs = node.attrs
        if node.op == 'BatchNorm' and \
                attrs.get('output_mean_var', False) and \
                not consumers.get((id(node), 1)) and \
                not consumers.get((id(node), 2)):
            attrs = dict(attrs)
            attrs['output_mean_var'] = False
            count[0] += 1
            changed = True
        nn = Node(node.op, node.name, attrs,
                  [mapped(e) for e in node.inputs])
        nn._extra_attr = node._extra_attr
        for j in range(node.num_outputs()):
            emap[(id(node), j)] = (nn, j)

    if not changed:
        return sym, 0
    return Symbol([mapped(e) for e in sym._outputs]), count[0]


# ---------------------------------------------------------------------------
# BN->relu onto the fused BN-ReLU Pallas kernel
# ---------------------------------------------------------------------------

def _register_bn_relu_op():
    from .ops.registry import register, _REGISTRY
    if '_bn_relu' in _REGISTRY:
        return
    from .ops.pallas_fused import fused_bn_relu

    def apply_fn(attrs, inputs, is_train, rng):
        data, gamma, beta, mov_mean, mov_var = inputs
        axes = (0,) + tuple(range(2, data.ndim))
        scale, bias, aux_updates = _bn_scale_bias(
            attrs, data, gamma, beta, mov_mean, mov_var, is_train,
            axes=axes)
        return [fused_bn_relu(data, scale, bias)], aux_updates

    def complete(attrs, in_shapes):
        d = in_shapes[0]
        if d is not None:
            for i in (1, 2):
                if in_shapes[i] is None:
                    in_shapes[i] = (d[1],)
        return in_shapes

    register('_bn_relu', apply_fn,
             input_names=lambda a: ['data', 'gamma', 'beta'],
             aux_names=lambda a: ['moving_mean', 'moving_var'],
             num_outputs=lambda a: 1,
             complete_shapes=complete,
             attr_defaults={'eps': 1e-3, 'momentum': 0.9,
                            'fix_gamma': True,
                            'use_global_stats': False},
             hint='bn_relu')


def fuse_bn_relu(sym: Symbol, is_train=False, mode='safe'):
    """Collapse the BN->relu chains the conv-targeted pass left behind
    (the relu feeds a pool / concat / non-fusable conv) into
    ``_bn_relu`` nodes lowered through the fused BN-ReLU Pallas kernel
    (``ops/pallas_fused.fused_bn_relu``): normalize+relu applied in
    VMEM on the streamed block when the Mosaic capability probe passes,
    the identical jnp broadcast form otherwise.  Run AFTER
    ``bn_relu_conv`` so conv-feeding chains take the stronger rewrite.
    Returns ``(symbol, rewrites)``."""
    _register_bn_relu_op()

    def try_fuse(n, consumer_list, mapped_entry):
        if n.op == 'Activation' and \
                n.attrs.get('act_type') == 'relu':
            bn, bidx = n.inputs[0]
            if (not bn.is_variable and bn.op == 'BatchNorm'
                    and bidx == 0
                    and len(consumer_list(bn)) == 1
                    and not bn.attrs.get('output_mean_var', False)):
                attrs = {
                    'eps': bn.attrs.get('eps', 1e-3),
                    'momentum': bn.attrs.get('momentum', 0.9),
                    'fix_gamma': bn.attrs.get('fix_gamma', True),
                    'use_global_stats':
                        bn.attrs.get('use_global_stats', False),
                }
                ins = [mapped_entry(e) for e in bn.inputs]
                fused = Node('_bn_relu', n.name, attrs, ins)
                fused._extra_attr = dict(n._extra_attr)
                return fused
        return None

    return _rewrite_counted(sym, try_fuse)


# ---------------------------------------------------------------------------
# elementwise-epilogue fusion — bias-add/relu/clip chains into the producer
# ---------------------------------------------------------------------------

_EPILOGUE_BASE_OPS = ('Convolution', 'FullyConnected', 'dot')
# two-operand elementwise steps admitted when the OTHER operand is a
# parameter variable (the bias/scale patterns); aliases listed too
# because node.op records the construction-time name
_EPILOGUE_BINARY = ('_plus', 'elemwise_add', 'broadcast_add',
                    'broadcast_plus', '_mul', 'elemwise_mul',
                    'broadcast_mul')


def _admissible_epilogue_step(nxt, cur):
    """Step descriptor when ``nxt`` (sole consumer of ``cur``) can fold
    into the producer's epilogue, else None."""
    if nxt.op == 'Activation':
        if nxt.attrs.get('act_type') != 'relu':
            return None
        if len(nxt.inputs) != 1 or nxt.inputs[0][0] is not cur:
            return None
        return {'node': nxt, 'y_index': 0, 'extra': None}
    if nxt.op == 'clip':
        if len(nxt.inputs) != 1 or nxt.inputs[0][0] is not cur:
            return None
        return {'node': nxt, 'y_index': 0, 'extra': None}
    if nxt.op in _EPILOGUE_BINARY:
        if len(nxt.inputs) != 2:
            return None
        sides = [i for i, (s, j) in enumerate(nxt.inputs)
                 if s is cur and j == 0]
        if len(sides) != 1:
            return None
        other = nxt.inputs[1 - sides[0]]
        if not other[0].is_variable:
            return None
        return {'node': nxt, 'y_index': sides[0], 'extra': other}
    return None


def _register_epilogue_op():
    from .ops.registry import register, _REGISTRY, get_op
    if '_fused_epilogue' in _REGISTRY:
        return

    def apply_fn(attrs, inputs, is_train, rng):
        base = get_op(attrs['base_op'])
        nbase = int(attrs['num_base_inputs'])
        base_attrs = base.canon_attrs(attrs['base_attrs'])
        steps = attrs['steps']
        lowered = _try_lower_epilogue(attrs, base_attrs, inputs, steps,
                                      nbase)
        if lowered is not None:
            return [lowered], {}
        # exact replay: the SAME op applies in the SAME order the
        # unfused graph ran them — bit-for-bit, the safe-pass contract
        outs, aux = base.apply(base_attrs, list(inputs[:nbase]),
                               is_train, rng)
        y = outs[0]
        ei = nbase
        for st in steps:
            op = get_op(st['op'])
            sattrs = op.canon_attrs(st['attrs'])
            if st['has_extra']:
                other = inputs[ei]
                ei += 1
                ins = [y, other] if st['y_index'] == 0 else [other, y]
            else:
                ins = [y]
            souts, _ = op.apply(sattrs, ins, is_train, rng)
            y = souts[0]
        return [y], aux

    def input_names(attrs):
        base = get_op(attrs['base_op'])
        names = list(base.input_names(attrs['base_attrs']))
        return names + ['ep%d' % i
                        for i in range(int(attrs.get('num_extra', 0)))]

    register('_fused_epilogue', apply_fn,
             input_names=input_names,
             num_outputs=lambda a: 1,
             attr_defaults={'num_extra': 0},
             hint='fused_epilogue')


def _try_lower_epilogue(attrs, base_attrs, inputs, steps, nbase):
    """Pallas lowering of a FullyConnected epilogue chain matching
    ``[bias-add?] [relu?] [clip?]`` — ``fused_dot_epilogue`` applies
    the chain to the fp32 accumulator in VMEM at the last K step.
    Only in AGGRESSIVE mode (the rewrite pass stamps ``lower_kernel``)
    and on the kernel paths (Mosaic capability probe passed or
    interpret forced): safe mode and reference mode keep the bit-exact
    replay — the blocked fp32 accumulation reorders the K sum, which
    would break the safe-level bit-for-bit contract.  Returns the
    lowered output or None."""
    if attrs['base_op'] != 'FullyConnected' or \
            not attrs.get('lower_kernel', False):
        return None
    from .ops import pallas_fused as _pf
    if _pf._mode() == 'reference':
        return None
    data, weight = inputs[0], inputs[1]
    no_bias = bool(base_attrs.get('no_bias', False))
    bias = None if no_bias else inputs[2]
    relu = False
    clip = None
    stage = 0           # 0: bias-add, 1: relu, 2: clip — forward-only
    ei = nbase
    for st in steps:
        if st['op'] in _EPILOGUE_BINARY:
            if stage > 0 or st['op'] not in (
                    '_plus', 'elemwise_add', 'broadcast_add',
                    'broadcast_plus'):
                return None
            extra = inputs[ei]
            ei += 1
            if extra.ndim != 1 or extra.shape[0] != weight.shape[0]:
                return None
            bias = extra if bias is None else bias + extra
            stage = 1
        elif st['op'] == 'Activation':
            if stage > 1:
                return None
            relu = True
            stage = 2
        elif st['op'] == 'clip':
            if stage > 2:
                return None     # second clip: fall back to the replay
            sattrs = st['attrs']
            if sattrs.get('a_min') is None or \
                    sattrs.get('a_max') is None:
                return None
            clip = (float(sattrs['a_min']), float(sattrs['a_max']))
            stage = 3
        else:
            return None
    x2 = data.reshape(data.shape[0], -1)
    return _pf.fused_dot_epilogue(x2, weight.T, bias, relu=relu,
                                  clip=clip)


def fuse_epilogues(sym: Symbol, is_train=False, mode='safe'):
    """Collapse elementwise chains following Convolution /
    FullyConnected / dot — parameter bias-adds, relu, clip — into ONE
    ``_fused_epilogue`` node carrying the chain as an epilogue attr.
    Safe by construction: the fused apply replays the identical ops in
    the identical order (bit-for-bit), and only single-consumer
    intermediates fold (nothing is recomputed, nothing externally
    visible disappears).  On the Pallas kernel paths a FullyConnected
    chain lowers to ``fused_dot_epilogue`` (the epilogue applied to the
    fp32 accumulator in VMEM).  Returns ``(symbol, chains fused)``."""
    _register_epilogue_op()
    nodes = sym.topo_nodes()
    consumers = {}
    for n in nodes:
        for s, j in n.inputs:
            consumers.setdefault((id(s), j), []).append(n)
    for s, j in sym._outputs:
        consumers.setdefault((id(s), j), []).append(None)

    chains = {}         # id(producer) -> (steps, tail node)
    in_chain = set()
    for n in nodes:
        if n.is_variable or n.op not in _EPILOGUE_BASE_OPS:
            continue
        steps = []
        cur = n
        while True:
            cons = consumers.get((id(cur), 0), [])
            if len(cons) != 1 or cons[0] is None:
                break
            st = _admissible_epilogue_step(cons[0], cur)
            if st is None:
                break
            steps.append(st)
            cur = cons[0]
        if steps:
            chains[id(n)] = (steps, cur)
            in_chain.update(id(st['node']) for st in steps)

    if not chains:
        return sym, 0

    emap = {}

    def mapped(entry):
        s, j = entry
        if s.is_variable:
            return (s, j)
        return emap[(id(s), j)]

    count = 0
    for n in nodes:
        if n.is_variable or id(n) in in_chain:
            continue
        chain = chains.get(id(n))
        if chain is None:
            nn = Node(n.op, n.name, n.attrs,
                      [mapped(e) for e in n.inputs])
            nn._extra_attr = n._extra_attr
            for j in range(n.num_outputs()):
                emap[(id(n), j)] = (nn, j)
            continue
        steps, tail = chain
        ins = [mapped(e) for e in n.inputs]
        descs = []
        extra = 0
        for st in steps:
            descs.append({'op': st['node'].op,
                          'attrs': dict(st['node'].attrs),
                          'y_index': st['y_index'],
                          'has_extra': st['extra'] is not None})
            if st['extra'] is not None:
                ins.append(mapped(st['extra']))
                extra += 1
        attrs = {'base_op': n.op, 'base_attrs': dict(n.attrs),
                 'num_base_inputs': len(n.inputs), 'steps': descs,
                 'num_extra': extra,
                 # kernel lowering reorders the K accumulation — only
                 # the aggressive (rtol-parity) tier may take it; safe
                 # keeps the bit-exact replay
                 'lower_kernel': mode == 'aggressive'}
        fused = Node('_fused_epilogue', tail.name, attrs, ins)
        fused._extra_attr = dict(tail._extra_attr)
        emap[(id(n), 0)] = (fused, 0)
        emap[(id(tail), 0)] = (fused, 0)
        count += 1

    return Symbol([mapped(e) for e in sym._outputs]), count


# ---------------------------------------------------------------------------
# the pass manager — sequencing, per-pass enable, stats, knob gating
# ---------------------------------------------------------------------------

class FusePass(object):
    """One named graph-rewrite pass: ``fn(sym, is_train) ->
    (sym, rewrites)``.  ``level`` gates it: 'safe' passes run under
    ``MXTPU_FUSE=safe`` and above (bit-for-bit oracle parity contract),
    'aggressive' only under ``aggressive`` (rtol-level parity — numeric
    reassociation inside the fused kernels)."""

    __slots__ = ('name', 'level', 'fn')

    def __init__(self, name, level, fn):
        assert level in ('safe', 'aggressive'), level
        self.name = name
        self.level = level
        self.fn = fn

    def __repr__(self):
        return 'FusePass(%s, %s)' % (self.name, self.level)


def _kernel_paths_live():
    """True when the Pallas kernel paths actually compile (a TPU,
    MXTPU_ASSUME_TPU, or interpret forced).  The kernel-LOWERED rewrites
    (``bn_relu_conv`` and its NHWC layout planning) only pay for
    themselves when their kernels are real: on the jnp reference path
    the fallback forms MATERIALIZE the normalize pass XLA would have
    fused into its neighbors (+13% step bytes measured on the
    check_fusion reference model), so those passes step aside and the
    graph keeps native ops XLA fuses itself."""
    from .ops import pallas_fused
    return pallas_fused._mode() != 'reference'


def _pass_bn_relu_conv(sym, is_train, mode='safe'):
    if not _kernel_paths_live():
        return sym, 0
    _register_fused_op()
    return _rewrite_counted(sym, _try_fuse_bn_relu_conv)


def _pass_nhwc_regions(sym, is_train, mode='safe'):
    if not _kernel_paths_live():
        return sym, 0
    return _nhwc_regions_counted(sym)


def default_passes():
    """The pipeline, in execution order.  Folding passes run before
    the pattern fusers (a folded conv->bn exposes no stale BN to the
    matchers); ``bn_relu`` runs after ``bn_relu_conv`` so conv-feeding
    chains take the stronger rewrite; layout planning runs last over
    the final op mix."""
    return [
        FusePass('constant_fold', 'safe', fold_constants),
        FusePass('dead_branch', 'safe', prune_dead_branches),
        FusePass('conv_bn_fold', 'aggressive', fold_conv_bn),
        FusePass('bn_relu_conv', 'aggressive', _pass_bn_relu_conv),
        FusePass('bn_relu', 'aggressive', fuse_bn_relu),
        FusePass('epilogue', 'safe', fuse_epilogues),
        FusePass('nhwc_regions', 'aggressive', _pass_nhwc_regions),
    ]


class PassManager(object):
    """Sequenced, stat-reporting pass pipeline.  ``run`` applies the
    enabled passes in order, records per-pass
    ``{rewrites, nodes_removed}`` into ``last_stats`` and reports them
    through perfwatch (``fuse.pass.<name>.*`` counters)."""

    def __init__(self, passes=None):
        self.passes = list(passes) if passes is not None \
            else default_passes()
        self.last_stats = None

    def run(self, sym, is_train, mode='safe', skip=()):
        stats = {}
        total = 0
        for p in self.passes:
            if p.name in skip:
                continue
            if p.level == 'aggressive' and mode != 'aggressive':
                continue
            before = len(sym.topo_nodes())
            out, n = p.fn(sym, is_train, mode)
            after = len(out.topo_nodes())
            stats[p.name] = {'rewrites': int(n),
                             'nodes_removed': max(0, before - after)}
            total += int(n)
            sym = out
        self.last_stats = {'mode': mode, 'is_train': bool(is_train),
                           'total_rewrites': total, 'passes': stats}
        from . import perfwatch
        perfwatch.note_fuse(mode, stats)
        return sym


_MANAGER = None


def default_manager() -> PassManager:
    global _MANAGER
    if _MANAGER is None:
        _MANAGER = PassManager()
    return _MANAGER


def last_run_stats():
    """Per-pass stats of the most recent pipeline run (None before the
    first): ``{'mode', 'is_train', 'total_rewrites', 'passes': {name:
    {'rewrites', 'nodes_removed'}}}`` — the check_fusion.py surface."""
    return None if _MANAGER is None else _MANAGER.last_stats


_MODES = ('off', 'safe', 'aggressive')


def fuse_mode():
    """Resolve the step-compiler mode from ``MXTPU_FUSE``; unset means
    'off'.  An unrecognized value raises loudly at program-build time:
    a misspelled perf knob silently meaning 'off' is how trajectories
    go blind."""
    raw = str(config.get('MXTPU_FUSE') or '').strip().lower() or 'off'
    if raw not in _MODES:
        raise ValueError('MXTPU_FUSE must be off|safe|aggressive, '
                         'got %r' % raw)
    return raw


def apply_fuse_passes(symbol: Symbol, is_train, mode=None) -> Symbol:
    """The step-compiler entry: run the pass pipeline over a symbol
    about to become a compiled program (``make_fit_step`` /
    ``make_eval_step`` / the Executor's one-program jit paths, and
    through them ``Predictor``).  ``mode`` None reads the knobs; 'off'
    returns the INPUT SYMBOL OBJECT untouched — zero graph surface,
    byte-identical downstream program."""
    if mode is None:
        mode = fuse_mode()
    if mode == 'off':
        return symbol
    skip = tuple(s.strip() for s in
                 str(config.get('MXTPU_FUSE_SKIP') or '').split(',')
                 if s.strip())
    manager = default_manager()
    known = {p.name for p in manager.passes}
    unknown = sorted(set(skip) - known)
    if unknown:
        # same loud-knob policy as fuse_mode: a typo'd skip name
        # silently leaving the pass ENABLED would poison a bisection
        raise ValueError('MXTPU_FUSE_SKIP names unknown passes %s '
                         '(have: %s)' % (unknown, sorted(known)))
    return manager.run(symbol, is_train, mode, skip=skip)
