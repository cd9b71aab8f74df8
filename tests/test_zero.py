"""ZeRO sharded optimizer (parallel/zero.py) on the 8-device CPU mesh:
the sharded update must produce bitwise-identical parameters to the
replicated single-device SGD-momentum update, and optimizer state must
actually be sharded (chunk-sized slots)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from mxnet_tpu.parallel.zero import (make_zero_sgd_momentum, zero_init,
                                     zero_state_size)
from mxnet_tpu.parallel.train_step import (make_sgd_momentum,
                                           sgd_momentum_init)

N = 8


@pytest.fixture
def mesh():
    if len(jax.devices()) < N:
        pytest.skip('needs %d devices' % N)
    return Mesh(np.array(jax.devices()[:N]), ('dp',))


def _params():
    rng = np.random.RandomState(0)
    return {
        'w1': jnp.asarray(rng.randn(13, 7).astype(np.float32)),  # pads
        'b1': jnp.asarray(rng.randn(7).astype(np.float32)),
        'w2': jnp.asarray(rng.randn(16, 16).astype(np.float32)),
    }


def test_state_is_sharded():
    params = _params()
    # fused momentum: ceil(91/8) + ceil(7/8) + ceil(256/8) lanes
    assert zero_state_size(params, N) == 12 + 1 + 32
    assert zero_init(params, N).shape == (45,)


def test_matches_replicated_update(mesh):
    from jax import shard_map
    params = _params()
    rng = np.random.RandomState(1)
    # per-device gradients (dp-sharded leading axis)
    grads_all = {k: jnp.asarray(
        rng.randn(N, *v.shape).astype(np.float32) * 0.1)
        for k, v in params.items()}

    lr, mom, wd, resc = 0.1, 0.9, 1e-3, 1.0 / N
    zero_update = make_zero_sgd_momentum('dp', N, lr=lr, momentum=mom,
                                         wd=wd, rescale_grad=resc)

    def step(params, grads):
        mom_shards = zero_init(params, N)
        new_p, _ = zero_update(params, grads, mom_shards)
        return new_p

    sharded = shard_map(
        step, mesh=mesh,
        in_specs=(P(), P('dp')),
        out_specs=P(), check_vma=False)
    got = sharded(params, grads_all)

    # reference: replicated update on the summed gradients
    ref_update = make_sgd_momentum(lr=lr, momentum=mom, wd=wd,
                                   rescale_grad=resc)
    summed = {k: g.sum(0) for k, g in grads_all.items()}
    want, _ = ref_update(params, summed, sgd_momentum_init(params))

    for k in params:
        np.testing.assert_allclose(np.asarray(got[k]),
                                   np.asarray(want[k]),
                                   rtol=1e-6, atol=1e-6, err_msg=k)


def test_two_steps_momentum_carries(mesh):
    from jax import shard_map
    params = _params()
    rng = np.random.RandomState(2)
    g1 = {k: jnp.asarray(rng.randn(N, *v.shape).astype(np.float32))
          for k, v in params.items()}
    g2 = {k: jnp.asarray(rng.randn(N, *v.shape).astype(np.float32))
          for k, v in params.items()}

    lr, mom, wd, resc = 0.05, 0.9, 0.0, 1.0 / N
    zero_update = make_zero_sgd_momentum('dp', N, lr=lr, momentum=mom,
                                         wd=wd, rescale_grad=resc)

    def two_steps(params, ga, gb):
        mom_shards = zero_init(params, N)
        p1, m1 = zero_update(params, ga, mom_shards)
        p2, _ = zero_update(p1, gb, m1)
        return p2

    got = shard_map(two_steps, mesh=mesh,
                    in_specs=(P(), P('dp'), P('dp')),
                    out_specs=P(), check_vma=False)(params, g1, g2)

    ref_update = make_sgd_momentum(lr=lr, momentum=mom, wd=wd,
                                   rescale_grad=resc)
    s1 = {k: g.sum(0) for k, g in g1.items()}
    s2 = {k: g.sum(0) for k, g in g2.items()}
    p1, st = ref_update(params, s1, sgd_momentum_init(params))
    want, _ = ref_update(p1, s2, st)
    for k in params:
        np.testing.assert_allclose(np.asarray(got[k]),
                                   np.asarray(want[k]),
                                   rtol=1e-5, atol=1e-6, err_msg=k)


def test_make_zero_train_step_matches_single_device(mesh):
    """End-to-end: the shard_map ZeRO step on a dp-sharded batch must
    match make_train_step on the full batch (MLP: no BN, so shard-local
    statistics cannot diverge)."""
    import jax.numpy as jnp
    from mxnet_tpu import sym
    from mxnet_tpu.parallel.zero import (make_zero_train_step,
                                         zero_opt_init)
    from mxnet_tpu.parallel.train_step import make_train_step

    data = sym.Variable('data')
    net = sym.FullyConnected(data, num_hidden=16, name='fc1')
    net = sym.Activation(net, act_type='relu')
    net = sym.FullyConnected(net, num_hidden=4, name='fc2')
    net = sym.SoftmaxOutput(net, name='softmax')

    rng = np.random.RandomState(3)
    batch_global = 4 * N
    params = {
        'fc1_weight': jnp.asarray(rng.randn(16, 8).astype(np.float32)
                                  * 0.3),
        'fc1_bias': jnp.zeros(16, jnp.float32),
        'fc2_weight': jnp.asarray(rng.randn(4, 16).astype(np.float32)
                                  * 0.3),
        'fc2_bias': jnp.zeros(4, jnp.float32),
    }
    batch = {
        'data': jnp.asarray(rng.rand(batch_global, 8)
                            .astype(np.float32)),
        'softmax_label': jnp.asarray(
            rng.randint(0, 4, batch_global).astype(np.float32)),
    }
    key = jax.random.PRNGKey(0)
    lr, mom_c, wd, resc = 0.1, 0.9, 1e-3, 1.0 / batch_global

    # donate=False: the test reuses `params` for the reference step
    # after the zero step (donated buffers would be invalidated)
    zstep = make_zero_train_step(net, mesh, 'dp', lr=lr,
                                 momentum=mom_c, wd=wd,
                                 rescale_grad=resc, donate=False)
    outs_z, p_z, _, opt_z = zstep(params, {},
                                  zero_opt_init(params, N), batch, key)

    from mxnet_tpu.parallel.train_step import (make_sgd_momentum,
                                               sgd_momentum_init)
    ref_step = make_train_step(
        net, make_sgd_momentum(lr=lr, momentum=mom_c, wd=wd,
                               rescale_grad=resc),
        ('data', 'softmax_label'), donate=False)
    outs_r, p_r, _, _ = ref_step(params, {}, sgd_momentum_init(params),
                                 batch, key)

    np.testing.assert_allclose(np.asarray(outs_z[0]),
                               np.asarray(outs_r[0]), rtol=1e-5,
                               atol=1e-6)
    for k in params:
        np.testing.assert_allclose(np.asarray(p_z[k]),
                                   np.asarray(p_r[k]), rtol=1e-5,
                                   atol=1e-6, err_msg=k)
    # two more steps through the zero path: state threading works
    outs_z, p_z, _, opt_z = zstep(p_z, {}, opt_z, batch, key)
    assert np.isfinite(np.asarray(outs_z[0])).all()


def test_make_zero_train_step_rejects_local_normalization(mesh):
    """normalization='batch' divides by the shard-local batch under
    shard_map — the builder must refuse instead of silently scaling
    gradients by the dp degree."""
    from mxnet_tpu import sym
    from mxnet_tpu.parallel.zero import make_zero_train_step
    data = sym.Variable('data')
    net = sym.FullyConnected(data, num_hidden=4, name='fc1')
    net = sym.SoftmaxOutput(net, name='softmax',
                            normalization='batch')
    with pytest.raises(ValueError, match='SHARD-local'):
        make_zero_train_step(net, mesh, 'dp')


def test_zero_step_with_fusion_parity(mesh, monkeypatch):
    """MXTPU_FUSE=aggressive composes with the sharded ZeRO step: fused
    and unfused runs under the same shard_map must produce identical
    parameters (both use shard-local BN statistics, so they are
    directly comparable)."""
    import jax.numpy as jnp
    from mxnet_tpu import sym
    from mxnet_tpu.parallel.zero import (make_zero_train_step,
                                         zero_opt_init)

    def build():
        data = sym.Variable('data')
        bn = sym.BatchNorm(data, name='bn0')
        act = sym.Activation(bn, act_type='relu')
        conv = sym.Convolution(act, kernel=(1, 1), num_filter=8,
                               no_bias=True, name='conv0')
        flat = sym.Flatten(conv)
        fc = sym.FullyConnected(flat, num_hidden=4, name='fc1')
        return sym.SoftmaxOutput(fc, name='softmax')

    rng = np.random.RandomState(5)
    batch_global = 2 * N
    params = {
        'bn0_gamma': jnp.ones(4, jnp.float32),
        'bn0_beta': jnp.zeros(4, jnp.float32),
        'conv0_weight': jnp.asarray(
            rng.randn(8, 4, 1, 1).astype(np.float32) * 0.3),
        'fc1_weight': jnp.asarray(
            rng.randn(4, 8 * 6 * 6).astype(np.float32) * 0.1),
        'fc1_bias': jnp.zeros(4, jnp.float32),
    }
    aux = {'bn0_moving_mean': jnp.zeros(4, jnp.float32),
           'bn0_moving_var': jnp.ones(4, jnp.float32)}
    batch = {
        'data': jnp.asarray(rng.rand(batch_global, 4, 6, 6)
                            .astype(np.float32)),
        'softmax_label': jnp.asarray(
            rng.randint(0, 4, batch_global).astype(np.float32)),
    }
    key = jax.random.PRNGKey(1)
    monkeypatch.setenv('MXTPU_FORCE_PALLAS_INTERPRET', '1')

    results = {}
    for fuse in ('off', 'aggressive'):
        monkeypatch.setenv('MXTPU_FUSE', fuse)
        step = make_zero_train_step(build(), mesh, 'dp', lr=0.1,
                                    rescale_grad=1.0 / batch_global,
                                    donate=False)
        _, new_p, new_aux, _ = step(params, aux,
                                    zero_opt_init(params, N), batch,
                                    key)
        results[fuse] = (new_p, new_aux)

    for k in params:
        np.testing.assert_allclose(
            np.asarray(results['off'][0][k]),
            np.asarray(results['aggressive'][0][k]),
            rtol=1e-5, atol=1e-6, err_msg=k)
    for k in aux:
        np.testing.assert_allclose(
            np.asarray(results['off'][1][k]),
            np.asarray(results['aggressive'][1][k]),
            rtol=1e-5, atol=1e-6, err_msg=k)


def test_zero_step_bf16_compute(mesh):
    """Mixed precision through the sharded step: bf16 fwd/bwd compute,
    f32 master params and momentum (the reference's fp16 discipline,
    test_dtype.py)."""
    import jax.numpy as jnp
    from mxnet_tpu import sym
    from mxnet_tpu.parallel.zero import (make_zero_train_step,
                                         zero_opt_init)
    data = sym.Variable('data')
    net = sym.FullyConnected(data, num_hidden=8, name='fc1')
    net = sym.SoftmaxOutput(net, name='softmax')
    rng = np.random.RandomState(7)
    bs = 2 * N
    params = {'fc1_weight': jnp.asarray(
                  rng.randn(8, 4).astype(np.float32) * 0.3),
              'fc1_bias': jnp.zeros(8, jnp.float32)}
    batch = {'data': jnp.asarray(rng.rand(bs, 4).astype(np.float32)),
             'softmax_label': jnp.asarray(
                 rng.randint(0, 8, bs).astype(np.float32))}
    step = make_zero_train_step(net, mesh, 'dp', lr=0.1,
                                rescale_grad=1.0 / bs,
                                compute_dtype=jnp.bfloat16,
                                donate=False)
    outs, p1, _, opt1 = step(params, {}, zero_opt_init(params, N),
                             batch, jax.random.PRNGKey(0))
    assert p1['fc1_weight'].dtype == jnp.float32   # master stays f32
    assert opt1.dtype == jnp.float32
    assert np.isfinite(np.asarray(outs[0])).all()
    # and the params actually moved
    assert float(np.max(np.abs(np.asarray(p1['fc1_weight'])
                               - np.asarray(params['fc1_weight'])))) > 0
