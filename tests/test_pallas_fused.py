"""Fused scale-bias matmul kernel (ops/pallas_fused.py): the Pallas
kernel (interpret mode on CPU) must match the plain jnp reference, and
the custom_vjp must match autodiff of the reference expression."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd


def _case(m=256, k=128, n=256, dtype=np.float32, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randn(m, k).astype(dtype) * 0.5,
            rng.randn(k, n).astype(dtype) * 0.5,
            (rng.rand(k).astype(dtype) + 0.5),
            rng.randn(k).astype(dtype) * 0.1)


def test_interpret_matches_reference(monkeypatch):
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_fused as pf
    x, w, s, b = _case()
    ref = np.asarray(pf._reference(jnp.asarray(x), jnp.asarray(w),
                                   jnp.asarray(s), jnp.asarray(b)))
    monkeypatch.setenv('MXTPU_FORCE_PALLAS_INTERPRET', '1')
    out = np.asarray(pf.fused_scale_bias_dot(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(s), jnp.asarray(b)))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_odd_shapes_fall_back():
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_fused as pf
    x, w, s, b = _case(m=37, k=19, n=23)
    out = np.asarray(pf.fused_scale_bias_dot(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(s), jnp.asarray(b)))
    ref = (x * s + b) @ w
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_custom_vjp_matches_autodiff():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_fused as pf
    x, w, s, b = _case(m=64, k=32, n=16)

    def loss_fused(x, w, s, b):
        return jnp.sum(jnp.sin(pf.fused_scale_bias_dot(x, w, s, b)))

    def loss_ref(x, w, s, b):
        return jnp.sum(jnp.sin(((x * s + b) @ w).astype(x.dtype)))

    args = tuple(jnp.asarray(v) for v in (x, w, s, b))
    g1 = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(*args)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(*args)
    for a, bb in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=1e-4, atol=1e-5)


def test_registered_as_nd_op():
    x, w, s, b = _case(m=8, k=4, n=6)
    out = nd.fused_scale_bias_dot(nd.array(x), nd.array(w),
                                  nd.array(s), nd.array(b))
    np.testing.assert_allclose(out.asnumpy(), (x * s + b) @ w,
                               rtol=2e-5, atol=2e-5)


def test_interpret_relu_variant(monkeypatch):
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_fused as pf
    x, w, s, b = _case(m=128, k=128, n=128, seed=3)
    ref = np.maximum(x * s + b, 0) @ w
    monkeypatch.setenv('MXTPU_FORCE_PALLAS_INTERPRET', '1')
    out = np.asarray(pf.fused_scale_bias_dot(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(s), jnp.asarray(b),
        relu=True))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_bn_relu_interpret_matches_reference(monkeypatch):
    """The fused BN-ReLU kernel (interpret mode) must match its jnp
    reference form on NCHW and 2-D inputs — the parity net that lets
    the kernel land blind and activate on a real TPU's Mosaic."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_fused as pf
    rng = np.random.RandomState(5)
    for shape in ((2, 64, 8, 8), (256, 128)):
        c = shape[1] if len(shape) > 2 else shape[-1]
        x = jnp.asarray(rng.randn(*shape).astype(np.float32))
        s = jnp.asarray((rng.rand(c) + 0.5).astype(np.float32))
        b = jnp.asarray(rng.randn(c).astype(np.float32))
        ref = np.asarray(pf._bn_relu_reference(x, s, b))
        monkeypatch.setenv('MXTPU_FORCE_PALLAS_INTERPRET', '1')
        out = np.asarray(pf.fused_bn_relu(x, s, b))
        monkeypatch.delenv('MXTPU_FORCE_PALLAS_INTERPRET')
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_bn_relu_custom_vjp_matches_autodiff():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_fused as pf
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(2, 16, 4, 4).astype(np.float32))
    s = jnp.asarray((rng.rand(16) + 0.5).astype(np.float32))
    b = jnp.asarray(rng.randn(16).astype(np.float32))

    def loss_fused(x, s, b):
        return jnp.sum(jnp.sin(pf.fused_bn_relu(x, s, b)))

    def loss_ref(x, s, b):
        return jnp.sum(jnp.sin(pf._bn_relu_reference(x, s, b)))

    g1 = jax.grad(loss_fused, argnums=(0, 1, 2))(x, s, b)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(x, s, b)
    for a, bb in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=1e-4, atol=1e-5)


def test_bn_relu_odd_shapes_fall_back(monkeypatch):
    """Shapes the block picker cannot tile route to the reference even
    under forced interpret — never an error."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_fused as pf
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(3, 7, 5, 5).astype(np.float32))
    s = jnp.asarray((rng.rand(7) + 0.5).astype(np.float32))
    b = jnp.asarray(rng.randn(7).astype(np.float32))
    monkeypatch.setenv('MXTPU_FORCE_PALLAS_INTERPRET', '1')
    out = np.asarray(pf.fused_bn_relu(x, s, b))
    np.testing.assert_allclose(
        out, np.asarray(pf._bn_relu_reference(x, s, b)),
        rtol=2e-5, atol=2e-5)


def test_dot_epilogue_interpret_matches_reference(monkeypatch):
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_fused as pf
    x, w, _, _ = _case(m=128, k=64, n=32, seed=9)
    b = np.random.RandomState(9).randn(32).astype(np.float32)
    ref = np.clip(np.maximum(x @ w + b, 0), -1.0, 1.0)
    monkeypatch.setenv('MXTPU_FORCE_PALLAS_INTERPRET', '1')
    out = np.asarray(pf.fused_dot_epilogue(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(b),
        relu=True, clip=(-1.0, 1.0)))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_dot_epilogue_custom_vjp_matches_autodiff():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_fused as pf
    x, w, _, _ = _case(m=32, k=16, n=8, seed=10)
    b = jnp.asarray(np.random.RandomState(10).randn(8).astype(
        np.float32))
    args = (jnp.asarray(x), jnp.asarray(w), b)
    g1 = jax.grad(lambda *a: jnp.sum(jnp.sin(
        pf.fused_dot_epilogue(*a, relu=True))), argnums=(0, 1, 2))(
        *args)
    g2 = jax.grad(lambda x, w, b: jnp.sum(jnp.sin(
        jnp.maximum(x @ w + b, 0))), argnums=(0, 1, 2))(*args)
    for a, bb in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=1e-4, atol=1e-5)


def test_small_channel_stage_uses_kernel(monkeypatch):
    """ResNet stage-1 shapes (C=64, F=64) must take the kernel path —
    the 64/32 block candidates exist exactly for them."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import pallas_fused as pf
    assert pf._block(64, 512) == 64
    assert pf._block(64, 256) == 64
    x, w, s, b = _case(m=256, k=64, n=64, seed=3)
    ref = np.asarray(pf._reference(jnp.asarray(x), jnp.asarray(w),
                                   jnp.asarray(s), jnp.asarray(b),
                                   relu=True))
    monkeypatch.setenv('MXTPU_FORCE_PALLAS_INTERPRET', '1')
    out = np.asarray(pf.fused_scale_bias_dot(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(s),
        jnp.asarray(b), relu=True))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
