"""Fused BN+relu+conv3x3 Pallas kernel (ops/pallas_conv.py): the real
kernel through the Pallas interpreter must match the jnp reference, the
custom_vjp must match autodiff of the reference, and undividable shapes
must fall back."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu.ops.pallas_conv import (fused_scale_bias_conv3x3,
                                       _reference)


def _inputs(n=2, h=8, w=8, c=64, f=64, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(n, h, w, c).astype(np.float32) * 0.5),
            jnp.asarray(rng.randn(3, 3, c, f).astype(np.float32) * 0.2),
            jnp.asarray(rng.rand(c).astype(np.float32) + 0.5),
            jnp.asarray(rng.randn(c).astype(np.float32) * 0.2))


@pytest.mark.parametrize('stride', [1, 2])
def test_interpret_matches_reference(monkeypatch, stride):
    monkeypatch.setenv('MXTPU_FORCE_PALLAS_INTERPRET', '1')
    x, w, s, b = _inputs()
    got = fused_scale_bias_conv3x3(x, w, s, b, stride=stride)
    want = _reference(x, w, s, b, stride, True)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_norelu_variant(monkeypatch):
    monkeypatch.setenv('MXTPU_FORCE_PALLAS_INTERPRET', '1')
    x, w, s, b = _inputs()
    got = fused_scale_bias_conv3x3(x, w, s, b, relu=False)
    want = _reference(x, w, s, b, 1, False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_undividable_channels_fall_back():
    # c=48 has no 64-divisible block: must silently use the reference
    x, w, s, b = _inputs(c=48, f=48)
    got = fused_scale_bias_conv3x3(x, w, s, b)
    want = _reference(x, w, s, b, 1, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize('stride', [1, 2])
def test_custom_vjp_matches_autodiff(stride):
    """Backward (relu mask + affine pullback + conv vjp) vs autodiff of
    the plain reference expression."""
    x, w, s, b = _inputs(n=1, h=6, w=6, c=48, f=48)

    def f_fused(x, w, s, b):
        return jnp.sum(fused_scale_bias_conv3x3(x, w, s, b,
                                                stride=stride) ** 2)

    def f_ref(x, w, s, b):
        return jnp.sum(_reference(x, w, s, b, stride, True) ** 2)

    g0 = jax.grad(f_ref, argnums=(0, 1, 2, 3))(x, w, s, b)
    g1 = jax.grad(f_fused, argnums=(0, 1, 2, 3))(x, w, s, b)
    for a, e in zip(g1, g0):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=1e-4, atol=1e-4)


def test_stride2_odd_dims_dispatch_to_xla(monkeypatch):
    """The reshape-factored stride-2 taps need even h/w; odd spatial
    dims must take the reference expression, even ones the kernel."""
    from mxnet_tpu.ops import pallas_conv as pc

    class _FakeTpu:
        platform = 'tpu'

    monkeypatch.setattr(pc.jax, 'devices', lambda: [_FakeTpu()])
    monkeypatch.delenv('MXTPU_FORCE_PALLAS_INTERPRET', raising=False)
    # dispatch SELECTION is under test (the kernel is stubbed below)
    monkeypatch.setattr(
        pc, '_pallas_conv',
        lambda *a, **k: (_ for _ in ()).throw(
            AssertionError('reached the kernel')))
    x, w, s, b = _inputs(h=9, w=9)  # odd spatial dims
    got = pc._dispatch(x, w, s, b, 2, True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_reference(x, w, s, b, 2,
                                                     True)))
    # even dims dispatch to the kernel for both strides
    x, w, s, b = _inputs()
    for stride in (1, 2):
        with pytest.raises(AssertionError, match='reached the kernel'):
            pc._dispatch(x, w, s, b, stride, True)
