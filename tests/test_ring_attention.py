"""Sequence/context parallelism tests: ring attention and Ulysses
all-to-all attention on the virtual 8-device mesh vs full attention."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mxnet_tpu.parallel.ring import (full_attention, make_ring_attention,
                                     make_ulysses_attention)


def _setup(B=2, H=4, T=32, D=8, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    return q, k, v


def _mesh(n=4):
    return Mesh(np.array(jax.devices()[:n]), ('seq',))


@pytest.mark.parametrize('causal', [False, True])
def test_ring_attention_matches_full(causal):
    q, k, v = _setup()
    mesh = _mesh(4)
    attn = make_ring_attention(mesh, 'seq', causal=causal)
    sh = NamedSharding(mesh, P(None, None, 'seq', None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
    got = np.asarray(attn(qs, ks, vs))
    want = np.asarray(full_attention(q, k, v, causal=causal))
    assert np.allclose(got, want, atol=2e-5), np.abs(got - want).max()


@pytest.mark.parametrize('causal', [False])
def test_ulysses_attention_matches_full(causal):
    q, k, v = _setup(H=8)
    mesh = _mesh(4)
    attn = make_ulysses_attention(mesh, 'seq', causal=causal)
    sh = NamedSharding(mesh, P(None, None, 'seq', None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
    got = np.asarray(attn(qs, ks, vs))
    want = np.asarray(full_attention(q, k, v, causal=causal))
    assert np.allclose(got, want, atol=2e-5), np.abs(got - want).max()


def test_ring_attention_8way():
    q, k, v = _setup(T=64)
    mesh = _mesh(8)
    attn = make_ring_attention(mesh, 'seq', causal=True)
    sh = NamedSharding(mesh, P(None, None, 'seq', None))
    got = np.asarray(attn(*(jax.device_put(x, sh) for x in (q, k, v))))
    want = np.asarray(full_attention(q, k, v, causal=True))
    assert np.allclose(got, want, atol=2e-5), np.abs(got - want).max()


def test_ring_attention_grad():
    """Gradients flow through the ring (vjp through ppermute/fori_loop)."""
    q, k, v = _setup(B=1, H=2, T=16, D=4)
    mesh = _mesh(4)
    from functools import partial
    from jax import shard_map
    from mxnet_tpu.parallel.ring import ring_attention
    spec = P(None, None, 'seq', None)

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec),
             out_specs=P(), check_vma=False)
    def loss(q, k, v):
        o = ring_attention(q, k, v, 'seq', causal=False)
        return jax.lax.psum(jnp.sum(o * o), 'seq')

    sh = NamedSharding(mesh, P(None, None, 'seq', None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
    g = jax.grad(lambda a, b, c: loss(a, b, c).sum())(qs, ks, vs)

    def ref_loss(q, k, v):
        o = full_attention(q, k, v)
        return jnp.sum(o * o)

    gref = jax.grad(ref_loss)(q, k, v)
    assert np.allclose(np.asarray(g), np.asarray(gref), atol=1e-4)
