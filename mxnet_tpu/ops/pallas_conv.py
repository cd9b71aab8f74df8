"""Fused BN-apply + relu + 3x3 convolution Pallas kernel.

``fused_scale_bias_conv(x, w, scale, bias) = conv3x3(relu(x*scale+bias), w)``
— the 3x3 case of "fold the normalize pass into the consuming conv"
(``pallas_fused.py`` is the 1x1/matmul case).  XLA cannot fuse a
reduction-fed elementwise prologue into a
convolution, so the normalized activation otherwise materializes in HBM
(one extra write + read of the full activation per conv).  Here the
affine + relu + zero-padding all happen in VMEM on the streamed block:
the raw activation crosses HBM once per filter block (f/bf, which is
1-2 at every ResNet stage) and the normalized copy never exists.

Kernel layout (NHWC / HWIO, the TPU-native choice):
  grid = (N, F/bf, C/bc), C sequential (fp32 accumulator scratch).
  Each step loads the FULL spatial extent for ``bc`` channels — ResNet
  3x3 stages are at most 56x56x64 bf16 ≈ 400 KB, far under the ~16 MB
  VMEM budget — pads it in VMEM, and accumulates the nine taps as
  (OH*OW, bc) x (bc, bf) MXU dots.  Stride 1 and 2 supported (shifted
  strided slices of the padded block).

Backward is plain JAX: the relu mask + affine pullback composed with
``jax.vjp`` of the linear convolution (XLA DCEs the unused primal, so
the cost is exactly the standard two backward convs).

The role equivalent in the reference is the cuDNN fused-epilogue conv
(``src/operator/cudnn_convolution-inl.h:638`` algo selection); the
fusion itself is TPU-original.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Mosaic's default scoped-VMEM limit is 16 MiB, and the nine unrolled
# taps overrun it at ResNet-50's largest stride-2 shape: on a v5e the
# bf16 56x56x128 stride-2 kernel was refused with "Scoped allocation
# with size 24.88M and limit 16.00M exceeded scoped vmem limit by
# 8.88M" (chip_smoke.py, PR 21).  A v5e core has 128 MiB of VMEM.
VMEM_LIMIT_BYTES = 64 * 2 ** 20


def _pick(total, pref):
    for b in sorted({pref, 256, 128, 64}, reverse=True):
        if b <= total and total % b == 0:
            return b
    return None


def _kernel(x_ref, w_ref, s_ref, b_ref, o_ref, acc_ref, *, nc, oh, ow,
            stride, relu):
    """One (image, filter-block) tile; C is the sequential grid axis."""
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xa = x_ref[0].astype(jnp.float32) * s_ref[...].astype(jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    if relu:
        xa = jnp.maximum(xa, 0.0)
    xa = xa.astype(x_ref.dtype)
    # zero padding (pad=1) applied in VMEM — x stays unpadded in HBM
    xa = jnp.pad(xa, ((1, 1), (1, 1), (0, 0)))
    nch = xa.shape[2]
    acc = acc_ref[...]
    for dy in range(3):
        for dx in range(3):
            if stride == 1:
                tap = jax.lax.slice(
                    xa, (dy, dx, 0), (dy + oh, dx + ow, nch))
            else:
                # stride 2 WITHOUT strided vector slices (Mosaic
                # rejects strides >= 2): contiguous slab, then factor
                # each spatial axis into (out, 2) and keep index 0.
                # Requires even h/w so dy+2*oh <= h+2 (see _dispatch).
                slab = jax.lax.slice(
                    xa, (dy, dx, 0), (dy + 2 * oh, dx + 2 * ow, nch))
                slab = slab.reshape(oh, 2, 2 * ow, nch)[:, 0]
                tap = slab.reshape(oh, ow, 2, nch)[:, :, 0]
            acc += jax.lax.dot_general(
                tap.reshape(oh * ow, -1), w_ref[dy, dx],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    acc_ref[...] = acc

    @pl.when(c == nc - 1)
    def _done():
        o_ref[...] = acc_ref[...].reshape(
            1, oh, ow, -1).astype(o_ref.dtype)


def _pallas_conv(x, w, scale, bias, stride, relu, bc, bf, interpret):
    n, h, wd, c = x.shape
    f = w.shape[3]
    oh = (h + 2 - 3) // stride + 1
    ow = (wd + 2 - 3) // stride + 1
    nc = c // bc
    grid = (n, f // bf, nc)
    kwargs = {}
    scratch = [pltpu.VMEM((oh * ow, bf), jnp.float32)]
    if not interpret:
        kwargs['compiler_params'] = pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary'),
            vmem_limit_bytes=VMEM_LIMIT_BYTES)
    return pl.pallas_call(
        functools.partial(_kernel, nc=nc, oh=oh, ow=ow, stride=stride,
                          relu=relu),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, h, wd, bc), lambda i, j, k: (i, 0, 0, k)),
            pl.BlockSpec((3, 3, bc, bf), lambda i, j, k: (0, 0, k, j)),
            pl.BlockSpec((1, bc), lambda i, j, k: (0, k)),
            pl.BlockSpec((1, bc), lambda i, j, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((1, oh, ow, bf),
                               lambda i, j, k: (i, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, oh, ow, f), x.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        **kwargs,
    )(x, w, scale.reshape(1, c), bias.reshape(1, c))


def _conv(xa, w, stride):
    return jax.lax.conv_general_dilated(
        xa, w, (stride, stride), ((1, 1), (1, 1)),
        dimension_numbers=('NHWC', 'HWIO', 'NHWC'))


def _reference(x, w, scale, bias, stride, relu):
    xa = x.astype(jnp.float32) * scale.astype(jnp.float32) \
        + bias.astype(jnp.float32)
    if relu:
        xa = jnp.maximum(xa, 0.0)
    return _conv(xa.astype(x.dtype), w, stride).astype(x.dtype)


def kernel_blocks(x_shape, f, stride):
    """The (bc, bf) channel blocks the kernel runs an NHWC ``x_shape``
    with ``f`` filters at, or None for a shape it does not take, which
    ``_dispatch`` routes to :func:`_reference`."""
    _, h, wd, c = x_shape
    if stride not in (1, 2):
        # the kernel's tap factoring is written for strides 1 and 2
        # only; anything else silently sampling wrong rows would be a
        # correctness bug
        return None
    if stride == 2 and (h % 2 or wd % 2):
        # the reshape-factored stride-2 taps read a 2*oh slab from the
        # pad-1 block, which only fits when h and w are even (always
        # true for the ResNet stage boundaries)
        return None
    bc, bf = _pick(c, 128), _pick(f, 256)
    if bc is None or bf is None:
        return None
    # VMEM guard: padded f32 activation block must stay well on-chip
    if (h + 2) * (wd + 2) * bc * 4 > 6 * 2 ** 20:
        return None
    return bc, bf


def _dispatch(x, w, scale, bias, stride, relu):
    from .. import config
    mode = config.pallas_mode()
    blocks = None if mode == 'reference' else \
        kernel_blocks(x.shape, w.shape[3], stride)
    if blocks is None:
        return _reference(x, w, scale, bias, stride, relu)
    return _pallas_conv(x, w, scale, bias, stride, relu, *blocks,
                        interpret=mode == 'interpret')


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused_conv_core(x, w, scale, bias, stride, relu):
    return _dispatch(x, w, scale, bias, stride, relu)


def _fwd(x, w, scale, bias, stride, relu):
    return _dispatch(x, w, scale, bias, stride, relu), (x, w, scale, bias)


def _bwd(stride, relu, res, g):
    x, w, scale, bias = res
    x32 = x.astype(jnp.float32)
    pre = x32 * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    xa = jnp.maximum(pre, 0.0) if relu else pre
    xa = xa.astype(x.dtype)
    # vjp of the LINEAR conv: primal result is dead code under jit
    _, conv_vjp = jax.vjp(lambda xa_, w_: _conv(xa_, w_, stride), xa, w)
    dxa, dw = conv_vjp(g.astype(x.dtype))
    dxa = dxa.astype(jnp.float32)
    if relu:
        dxa = dxa * (pre > 0)
    dx = (dxa * scale.astype(jnp.float32)).astype(x.dtype)
    dscale = jnp.sum(dxa * x32, axis=(0, 1, 2)).astype(scale.dtype)
    dbias = jnp.sum(dxa, axis=(0, 1, 2)).astype(bias.dtype)
    return dx, dw.astype(w.dtype), dscale, dbias


_fused_conv_core.defvjp(_fwd, _bwd)


def fused_scale_bias_conv3x3(x, w, scale, bias, stride=1, relu=True):
    """``conv3x3(relu(x*scale+bias), w)`` with the affine+relu+padding
    applied in VMEM on the streamed block.  ``x`` NHWC, ``w`` HWIO,
    pad fixed at 1 (the ResNet 3x3 contract)."""
    return _fused_conv_core(x, w, scale, bias, int(stride), bool(relu))
