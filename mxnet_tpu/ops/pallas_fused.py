"""Fused scale-bias matmul Pallas kernel — the BN-into-matmul primitive.

``fused_scale_bias_dot(x, w, scale, bias) = ((x * scale + bias) @ w)``
computes a per-feature affine transform (exactly BatchNorm's inference/
train *apply* step, with ``scale = gamma * rsqrt(var+eps)`` and
``bias = beta - mean * scale``) fused into the consuming matmul — the
1x1-convolution case of "fold the normalize pass into the next conv"
(a 1x1 conv IS this matmul with ``x = NHWC->(N*H*W, C)``).

On a memory-bound graph the separate BN-apply pass costs one extra HBM
read + write of the activation; here the affine happens in VMEM on the
streamed block, so the activation is read once.  The reference reached
the same class of fusion through cuDNN's fused conv epilogues.

Forward is a ``pl.pallas_call`` tiling (M, K) x (K, N) with fp32
accumulation on the MXU; scale/bias ride along the K axis.  Backward is
expressed in plain JAX (matmuls XLA already emits optimally):
``dx = (g @ w^T) * scale``, ``dw = (x*scale+bias)^T @ g``,
``dscale = sum_m x * (g @ w^T)``, ``dbias = sum_m g @ w^T``.

Off-TPU the public entry falls back to the identical jnp expression;
``MXTPU_FORCE_PALLAS_INTERPRET=1`` runs the real kernel through the
Pallas interpreter in tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .registry import register_simple


def _block(t, pref):
    # 64/32 keep the small-channel ResNet stages (C=64) on the kernel
    # path — below a full 128 MXU tile but still far better than
    # falling back to a materializing XLA expression
    for b in sorted({pref, 512, 256, 128, 64, 32}, reverse=True):
        if b <= t and t % b == 0:
            return b
    return None


def _kernel(x_ref, w_ref, s_ref, b_ref, o_ref, acc_ref, *, nk, relu):
    """Grid (M/bm, N/bn, K/bk); K is the sequential axis, the fp32
    accumulator lives in VMEM scratch across it."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xa = x_ref[...].astype(jnp.float32) * \
        s_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    if relu:
        xa = jnp.maximum(xa, 0.0)
    acc_ref[...] += jax.lax.dot_general(
        xa.astype(x_ref.dtype), w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pallas_forward(x, w, scale, bias, bm, bn, bk, interpret,
                    relu=False):
    m, k = x.shape
    _, n = w.shape
    nk = k // bk
    grid = (m // bm, n // bn, nk)
    kwargs = {}
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    if not interpret:
        kwargs['compiler_params'] = pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary'))
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk, relu=relu),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bk), lambda i, j, kk: (0, kk)),
            pl.BlockSpec((1, bk), lambda i, j, kk: (0, kk)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        **kwargs,
    )(x, w, scale.reshape(1, k), bias.reshape(1, k))


def _reference(x, w, scale, bias, relu=False):
    xa = x * scale + bias
    if relu:
        xa = jnp.maximum(xa, 0)
    return (xa @ w).astype(x.dtype)


def _mode():
    """The shared kernel-dispatch decision (``config.pallas_mode``)."""
    from .. import config
    return config.pallas_mode()


def dot_blocks(m, k, n):
    """The (bm, bn, bk) blocks the matmul kernels run an (m, k) x (k, n)
    product at, or None for a shape they do not take, which the
    dispatchers route to the jnp reference."""
    blocks = _block(m, 512), _block(n, 256), _block(k, 512)
    return None if None in blocks else blocks


def _dispatch(x, w, scale, bias, relu):
    mode = _mode()
    blocks = None if mode == 'reference' else \
        dot_blocks(x.shape[0], x.shape[1], w.shape[1])
    if blocks is None:
        return _reference(x, w, scale, bias, relu)
    return _pallas_forward(x, w, scale, bias, *blocks,
                           interpret=mode == 'interpret', relu=relu)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _fused_core(x, w, scale, bias, relu):
    return _dispatch(x, w, scale, bias, relu)


def _fwd(x, w, scale, bias, relu):
    return _dispatch(x, w, scale, bias, relu), (x, w, scale, bias)


def _bwd(relu, res, g):
    x, w, scale, bias = res
    g32 = g.astype(jnp.float32)
    gx = g32 @ w.astype(jnp.float32).T        # d(loss)/d(xa)@pre-matmul
    xa = x.astype(jnp.float32) * scale + bias
    if relu:
        mask = (xa > 0).astype(jnp.float32)
        dw_lhs = jnp.maximum(xa, 0)
        gx = gx * mask
    else:
        dw_lhs = xa
    dx = (gx * scale).astype(x.dtype)
    dw = (dw_lhs.T @ g32).astype(w.dtype)
    dscale = jnp.sum(gx * x, axis=0).astype(scale.dtype)
    dbias = jnp.sum(gx, axis=0).astype(bias.dtype)
    return dx, dw, dscale, dbias


_fused_core.defvjp(_fwd, _bwd)


def fused_scale_bias_dot(x, w, scale, bias, relu=False):
    """((x * scale + bias) [-> relu]) @ w with the affine (and relu)
    applied in VMEM on the streamed block."""
    return _fused_core(x, w, scale, bias, bool(relu))


register_simple('fused_scale_bias_dot', fused_scale_bias_dot, ninputs=4,
                input_names=['data', 'weight', 'scale', 'bias'],
                attr_defaults={'relu': False})


# ---------------------------------------------------------------------------
# Fused BN-ReLU (elementwise): relu(x * scale + bias), per-channel affine
# ---------------------------------------------------------------------------
#
# The standalone BatchNorm->relu chains the bn_relu_conv pass cannot
# touch (the relu feeds a pool / concat / non-fusable conv).  The kernel
# applies the normalize+relu in VMEM on the streamed block — one HBM
# read+write of the activation instead of three.  Channels-last 2D
# tiling (M, C); the public entry reshapes NCHW around the kernel only
# on the kernel paths (the jnp reference form broadcasts in place).

def _bn_relu_kernel(x_ref, s_ref, b_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    y = x * s_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.maximum(y, 0.0).astype(o_ref.dtype)


def _bn_relu_pallas(x2d, scale, bias, bm, bc, interpret):
    m, c = x2d.shape
    return pl.pallas_call(
        _bn_relu_kernel,
        grid=(m // bm, c // bc),
        in_specs=[
            pl.BlockSpec((bm, bc), lambda i, j: (i, j)),
            pl.BlockSpec((1, bc), lambda i, j: (0, j)),
            pl.BlockSpec((1, bc), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, c), x2d.dtype),
        interpret=interpret,
    )(x2d, scale.reshape(1, c), bias.reshape(1, c))


def _bn_relu_reference(x, scale, bias):
    """Per-channel (axis 1; axis -1 for 2D) affine + relu — the exact
    jnp form of the fused kernel."""
    bshape = (1, -1) + (1,) * (x.ndim - 2)
    y = x.astype(jnp.float32) * scale.astype(jnp.float32).reshape(bshape) \
        + bias.astype(jnp.float32).reshape(bshape)
    return jnp.maximum(y, 0.0).astype(x.dtype)


def bn_relu_blocks(m, c):
    """The (bm, bc) blocks the BN-ReLU kernel runs an (m, c) view at, or
    None for a shape it does not take (routed to the jnp reference)."""
    blocks = _block(m, 512), _block(c, 256)
    return None if None in blocks else blocks


def _bn_relu_dispatch(x, scale, bias):
    mode = _mode()
    if mode == 'reference':
        return _bn_relu_reference(x, scale, bias)
    interpret = mode == 'interpret'
    # kernel path: channels-last 2D view.  NCHW pays one transpose pair
    # here — on the kernel paths the NHWC region pass keeps fused
    # chains channels-last so the transposes cancel in practice.
    if x.ndim > 2:
        perm = (0,) + tuple(range(2, x.ndim)) + (1,)
        x2d = jnp.transpose(x, perm).reshape(-1, x.shape[1])
    else:
        x2d = x
    blocks = bn_relu_blocks(*x2d.shape)
    if blocks is None:
        return _bn_relu_reference(x, scale, bias)
    y2d = _bn_relu_pallas(x2d, scale, bias, *blocks, interpret)
    if x.ndim > 2:
        spatial = x.shape[2:]
        y = y2d.reshape((x.shape[0],) + spatial + (x.shape[1],))
        inv = (0, x.ndim - 1) + tuple(range(1, x.ndim - 1))
        return jnp.transpose(y, inv)
    return y2d


@jax.custom_vjp
def _bn_relu_core(x, scale, bias):
    return _bn_relu_dispatch(x, scale, bias)


def _bn_relu_fwd(x, scale, bias):
    return _bn_relu_dispatch(x, scale, bias), (x, scale, bias)


def _bn_relu_bwd(res, g):
    x, scale, bias = res
    bshape = (1, -1) + (1,) * (x.ndim - 2)
    axes = (0,) + tuple(range(2, x.ndim))
    x32 = x.astype(jnp.float32)
    s32 = scale.astype(jnp.float32).reshape(bshape)
    pre = x32 * s32 + bias.astype(jnp.float32).reshape(bshape)
    gm = g.astype(jnp.float32) * (pre > 0)
    dx = (gm * s32).astype(x.dtype)
    dscale = jnp.sum(gm * x32, axis=axes).astype(scale.dtype)
    dbias = jnp.sum(gm, axis=axes).astype(bias.dtype)
    return dx, dscale, dbias


_bn_relu_core.defvjp(_bn_relu_fwd, _bn_relu_bwd)


def fused_bn_relu(x, scale, bias):
    """``relu(x * scale + bias)`` with a per-channel affine (channel =
    axis 1 for >=3-D inputs, the trailing axis for 2-D) applied in VMEM
    on the streamed block.  The BN *apply* step with the statistics
    pre-folded to (scale, bias) — the elementwise sibling of
    :func:`fused_scale_bias_dot`."""
    return _bn_relu_core(x, scale, bias)


register_simple('fused_bn_relu', fused_bn_relu, ninputs=3,
                input_names=['data', 'scale', 'bias'])


# ---------------------------------------------------------------------------
# Fused dot-epilogue: (x @ w) [+ bias] [-> relu] [-> clip] in VMEM
# ---------------------------------------------------------------------------
#
# The OUTPUT-side counterpart of fused_scale_bias_dot's input prologue:
# the bias-add / relu / clip chain following a FullyConnected/dot is
# applied to the fp32 accumulator at the last K step, so the matmul
# result crosses HBM exactly once with the epilogue already folded in —
# the cuDNN fused-epilogue discipline the elementwise-epilogue fusion
# pass (fuse.py) lowers to on the kernel paths.

def _dot_epi_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, nk, relu,
                    clip_lo, clip_hi):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        y = acc_ref[...] + b_ref[...].astype(jnp.float32)
        if relu:
            y = jnp.maximum(y, 0.0)
        if clip_lo is not None:
            y = jnp.clip(y, clip_lo, clip_hi)
        o_ref[...] = y.astype(o_ref.dtype)


def _dot_epi_pallas(x, w, bias, bm, bn, bk, interpret, relu, clip):
    m, k = x.shape
    n = w.shape[1]
    nk = k // bk
    clip_lo, clip_hi = clip if clip is not None else (None, None)
    kwargs = {}
    if not interpret:
        kwargs['compiler_params'] = pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary'))
    return pl.pallas_call(
        functools.partial(_dot_epi_kernel, nk=nk, relu=relu,
                          clip_lo=clip_lo, clip_hi=clip_hi),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        **kwargs,
    )(x, w, bias.reshape(1, n))


def _dot_epi_reference(x, w, bias, relu, clip):
    y = (x @ w).astype(x.dtype) + bias
    if relu:
        y = jnp.maximum(y, 0)
    if clip is not None:
        y = jnp.clip(y, clip[0], clip[1])
    return y


def _dot_epi_dispatch(x, w, bias, relu, clip):
    mode = _mode()
    blocks = None if mode == 'reference' else \
        dot_blocks(x.shape[0], x.shape[1], w.shape[1])
    if blocks is None:
        return _dot_epi_reference(x, w, bias, relu, clip)
    return _dot_epi_pallas(x, w, bias, *blocks, mode == 'interpret',
                           relu, clip)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _dot_epi_core(x, w, bias, relu, clip):
    return _dot_epi_dispatch(x, w, bias, relu, clip)


def _dot_epi_fwd(x, w, bias, relu, clip):
    return _dot_epi_dispatch(x, w, bias, relu, clip), (x, w, bias)


def _dot_epi_bwd(relu, clip, res, g):
    x, w, bias = res
    x32, w32 = x.astype(jnp.float32), w.astype(jnp.float32)
    pre = x32 @ w32 + bias.astype(jnp.float32)
    z = jnp.maximum(pre, 0.0) if relu else pre
    gm = g.astype(jnp.float32)
    if clip is not None:
        gm = gm * ((z > clip[0]) & (z < clip[1]))
    if relu:
        gm = gm * (pre > 0)
    dx = (gm @ w32.T).astype(x.dtype)
    dw = (x32.T @ gm).astype(w.dtype)
    dbias = jnp.sum(gm, axis=0).astype(bias.dtype)
    return dx, dw, dbias


_dot_epi_core.defvjp(_dot_epi_fwd, _dot_epi_bwd)


def fused_dot_epilogue(x, w, bias=None, relu=False, clip=None):
    """``(x @ w) [+ bias] [-> relu] [-> clip(lo, hi)]`` with the
    elementwise epilogue applied to the fp32 accumulator in VMEM at the
    last K step.  ``clip`` is a (lo, hi) pair or None."""
    if bias is None:
        bias = jnp.zeros((w.shape[1],), x.dtype)
    clip = (float(clip[0]), float(clip[1])) if clip is not None else None
    return _dot_epi_core(x, w, bias, bool(relu), clip)


register_simple('fused_dot_epilogue', fused_dot_epilogue, ninputs=3,
                input_names=['data', 'weight', 'bias'],
                attr_defaults={'relu': False, 'clip': None})
