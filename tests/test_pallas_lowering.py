"""Offline Mosaic verification of every Pallas kernel, on CPU.

``jax.jit(f).trace(...).lower(lowering_platforms=('tpu',))`` builds and
VERIFIES the Mosaic module client-side — no TPU needed.  This is the
gate interpret-mode tests cannot provide: Mosaic rejects constructs the
interpreter happily runs (discovered on-chip in round 4, when the 3x3
stride-2 conv kernel's strided vector slices failed with
``VerificationError: strides confined to [1, 2)``).  Every new Pallas
kernel MUST get a cross-lowering case here, at the shapes its callers
make.  What this check cannot see is Mosaic's layout and VMEM passes,
which run only on the chip: ``chip_smoke.py`` compiles the same shapes
there.

``MXTPU_ASSUME_TPU=1`` makes the dispatch layers take the kernel path
without a TPU attached (config.py).
"""

import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(autouse=True)
def _assume_tpu(monkeypatch):
    monkeypatch.setenv('MXTPU_ASSUME_TPU', '1')
    monkeypatch.delenv('MXTPU_FORCE_PALLAS_INTERPRET', raising=False)


def lower_tpu(fn, *args):
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=('tpu',)).as_text()


def _kernel_count(txt):
    return txt.count('tpu_custom_call')


@pytest.mark.parametrize('c,f', [(64, 64), (128, 256), (256, 512)])
def test_conv3x3_s1_verifies(c, f):
    from mxnet_tpu.ops import pallas_conv as pc
    x = jnp.ones((2, 16, 16, c), jnp.bfloat16)
    w = jnp.ones((3, 3, c, f), jnp.bfloat16)
    s = jnp.ones((c,), jnp.float32)
    txt = lower_tpu(
        lambda x, w, s, b: pc.fused_scale_bias_conv3x3(x, w, s, b, 1,
                                                       True),
        x, w, s, s)
    assert _kernel_count(txt) >= 1


def test_conv3x3_s2_verifies():
    """stride-2 via reshape-factored taps (Mosaic rejects strided
    vector slices, so the kernel factors each spatial axis into
    (out, 2) and keeps index 0)."""
    from mxnet_tpu.ops import pallas_conv as pc
    x = jnp.ones((2, 16, 16, 64), jnp.bfloat16)
    w = jnp.ones((3, 3, 64, 128), jnp.bfloat16)
    s = jnp.ones((64,), jnp.float32)
    txt = lower_tpu(
        lambda x, w, s, b: pc.fused_scale_bias_conv3x3(x, w, s, b, 2,
                                                       True),
        x, w, s, s)
    assert _kernel_count(txt) >= 1


# (height, in channels, filters, stride): every 3x3 convolution of
# ResNet-50 at 224x224, the list chip_smoke.py compiles on the chip
# (tests/test_chip_smoke.py pins it against the model)
RESNET50_CONV3X3 = [(56, 64, 64, 1), (56, 128, 128, 2), (28, 128, 128, 1),
                    (28, 256, 256, 2), (14, 256, 256, 1),
                    (14, 512, 512, 2), (7, 512, 512, 1)]


@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize('h,c,f,stride', RESNET50_CONV3X3)
def test_conv3x3_resnet50_sizes_verify(h, c, f, stride, dtype):
    """The real spatial sizes: ow of 14 and 7 is not a multiple of the
    8-row tile, which the 16x16 cases above never exercise."""
    from mxnet_tpu.ops import pallas_conv as pc
    x = jnp.ones((2, h, h, c), dtype)
    w = jnp.ones((3, 3, c, f), dtype)
    s = jnp.ones((c,), jnp.float32)
    txt = lower_tpu(
        lambda x, w, s, b: pc.fused_scale_bias_conv3x3(x, w, s, b,
                                                       stride, True),
        x, w, s, s)
    assert _kernel_count(txt) == 1
    # the chip refused the bf16 56x56x128 stride-2 kernel under
    # Mosaic's default 16 MiB scoped-VMEM limit (24.88 MiB needed), so
    # the kernel asks for its own limit
    assert '\\22size\\22: %d' % pc.VMEM_LIMIT_BYTES in txt


def test_conv3x3_s2_odd_dims_lowers_without_kernel():
    """odd spatial dims cannot use the reshape-factored taps; the
    dispatch falls back to the XLA expression and still lowers."""
    from mxnet_tpu.ops import pallas_conv as pc
    x = jnp.ones((2, 15, 15, 64), jnp.bfloat16)
    w = jnp.ones((3, 3, 64, 128), jnp.bfloat16)
    s = jnp.ones((64,), jnp.float32)
    txt = lower_tpu(
        lambda x, w, s, b: pc.fused_scale_bias_conv3x3(x, w, s, b, 2,
                                                       True),
        x, w, s, s)
    assert _kernel_count(txt) == 0


@pytest.mark.parametrize('m,k,n', [(128, 64, 64), (256, 128, 512)])
def test_fused_matmul_verifies(m, k, n):
    from mxnet_tpu.ops import pallas_fused as pf
    x = jnp.ones((m, k), jnp.bfloat16)
    w = jnp.ones((k, n), jnp.bfloat16)
    s = jnp.ones((k,), jnp.float32)
    txt = lower_tpu(
        lambda x, w, s, b: pf.fused_scale_bias_dot(x, w, s, b,
                                                   relu=True),
        x, w, s, s)
    assert _kernel_count(txt) >= 1


def test_flash_attention_verifies():
    from mxnet_tpu.parallel.ring import full_attention
    q = jnp.ones((1, 2, 256, 64), jnp.bfloat16)
    txt = lower_tpu(lambda q: full_attention(q, q, q, causal=True), q)
    assert _kernel_count(txt) >= 1


@pytest.mark.parametrize('b,h,t,d', [(16, 8, 512, 64),
                                     (1, 8, 2048, 128)])
def test_flash_attention_backward_verifies(b, h, t, d):
    """The backward recomputes probabilities blockwise in plain JAX from
    the forward kernel's saved log-sum-exp, so its lowering carries the
    forward custom call; the shapes are chip_smoke.py's."""
    from mxnet_tpu.ops.pallas_attention import flash_attention
    q = jnp.ones((b, h, t, d), jnp.bfloat16)
    grad = jax.grad(
        lambda q, k, v: flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))
    assert _kernel_count(lower_tpu(grad, q, q, q)) >= 1


# the gated delta rule's segment in kimi_linear_fit_8k (2 sequences, 16
# chunks of 64 tokens, 32 heads of 128), the shape chip_smoke.py compiles on
# the chip; and a segment of two chunks of 16 with an odd number of heads
KDA_SEGMENTS = [(2, 1024, 32, 128, 64), (1, 32, 3, 128, 16)]


def _kda_segment(n, rows, heads, d, dtype):
    wide = jnp.ones((n, rows, heads, d), dtype)
    return (wide, wide, wide, -jnp.ones((n, rows, heads, d), jnp.float32),
            jnp.ones((n, rows, heads), jnp.float32),
            jnp.zeros((n, heads, d, d), jnp.float32))


@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize('n,rows,heads,d,chunk', KDA_SEGMENTS)
def test_kda_rule_forward_verifies(n, rows, heads, d, chunk, dtype):
    from mxnet_tpu.ops import lm, pallas_kda
    assert lm._rule_in_kernel(rows, d, d, chunk, dtype)
    txt = lower_tpu(
        lambda *a: pallas_kda.rule_segment(*a, chunk, lm.KDA_SUB,
                                           lm.KDA_DECAY_FLOOR),
        *_kda_segment(n, rows, heads, d, dtype))
    assert _kernel_count(txt) == 1
    assert '\\22size\\22: %d' % pallas_kda.VMEM_LIMIT_BYTES in txt


@pytest.mark.parametrize('dtype', [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize('n,rows,heads,d,chunk', KDA_SEGMENTS)
def test_kda_rule_backward_verifies(n, rows, heads, d, chunk, dtype):
    """Differentiated, a segment is the forward kernel once more, writing
    the state every chunk entered with, and the backward kernel."""
    from mxnet_tpu.ops import lm, pallas_kda
    args = _kda_segment(n, rows, heads, d, dtype)

    def both(*a):
        out, back = jax.vjp(
            lambda *b: pallas_kda.rule_segment(*b, chunk, lm.KDA_SUB,
                                               lm.KDA_DECAY_FLOOR), *a)
        return back(out)
    assert _kernel_count(lower_tpu(both, *args)) == 2


def test_kda_rule_outside_the_predicate_lowers_without_kernel():
    """Heads of 64 channels are no column block of the projections: the
    rule takes the jnp form and still lowers."""
    from mxnet_tpu.ops import lm
    q, k, v, g, beta, _ = _kda_segment(1, 32, 2, 64, jnp.bfloat16)
    assert not lm._rule_in_kernel(32, 64, 64, 16, jnp.bfloat16)
    txt = lower_tpu(lambda *a: lm.delta_rule_chunked(*a, chunk_size=16)[0],
                    q, k, v, g, beta)
    assert _kernel_count(txt) == 0


def test_fused_resnet50_train_step_verifies(monkeypatch):
    """The full MXTPU_FUSE=aggressive train step — every rewritten conv
    with its real shape class — must pass Mosaic verification, and the
    NHWC-region pass must keep fused chains channels-last (without it
    every fused node is sandwiched in NCHW<->NHWC activation
    transposes, 389 at bs=8, which custom calls cannot absorb as
    layouts; with it only foldable matmul/weight operand transposes
    and a couple of region boundaries remain, ~187)."""
    monkeypatch.setenv('MXTPU_FUSE', 'aggressive')
    from mxnet_tpu import models
    from mxnet_tpu.parallel.train_step import (
        make_train_step, make_sgd_momentum, sgd_momentum_init)
    # bs=8: below that, small spatial*batch products fail the
    # kernels' block-divisibility guards and dispatch to XLA,
    # shrinking the kernel count
    sym = models.get_symbol('resnet-50', num_classes=1000,
                            stem='space_to_depth')
    dshape = (8, 3, 224, 224)
    arg_shapes, _, aux_shapes = sym.infer_shape(data=dshape)
    # only shapes and dtypes reach the lowering: no values are drawn
    params = {name: jnp.zeros(shape, jnp.float32)
              for name, shape in zip(sym.list_arguments(), arg_shapes)
              if name not in ('data', 'softmax_label')}
    aux = {name: jnp.ones(shape, jnp.float32)
           for name, shape in zip(sym.list_auxiliary_states(),
                                  aux_shapes)}
    batch = {'data': jnp.zeros(dshape, jnp.bfloat16),
             'softmax_label': jnp.zeros(dshape[:1], jnp.float32)}
    opt = make_sgd_momentum(lr=0.05, momentum=0.9, wd=1e-4,
                            rescale_grad=0.125)
    step = make_train_step(sym, opt, ('data', 'softmax_label'),
                           compute_dtype=jnp.bfloat16)
    txt = step.trace(params, aux, sgd_momentum_init(params), batch,
                     jax.random.PRNGKey(0)).lower(
        lowering_platforms=('tpu',)).as_text()
    assert _kernel_count(txt) >= 40, _kernel_count(txt)
    n = txt.count('stablehlo.transpose')
    assert n < 260, 'transpose sandwiches are back: %d' % n
