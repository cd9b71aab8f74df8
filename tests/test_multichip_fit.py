"""dp×tp sharded ``Module.fit`` — the multi-chip product path
(docs/parallel.md).

The conftest pins 8 virtual CPU devices, so the real mesh machinery
runs hermetically: ``fit(mesh='4x2', partition='auto')`` jits the
fused step with NamedSharding in/out shardings (batch over dp, params
tp-sharded, optimizer state ZeRO-sharded over dp) and must train the
SAME model as the single-device fused fit — the mesh is a layout,
never different math.  ``mesh='1x1'`` is held to the stricter depth-1
discipline: bit-for-bit identical params and metric values.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import instrument
from mxnet_tpu.base import MXNetError

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_global_state():
    """instrument/perfwatch state is process-global: restore it so the
    rest of the suite (knobs-off guards, overhead floors) is
    unaffected by the metrics these tests turn on."""
    from mxnet_tpu import perfwatch
    prof = instrument.profiling_enabled()
    met = instrument.metrics_enabled()
    yield
    perfwatch.set_enabled(False)
    perfwatch.clear_executables()
    instrument.set_profiling(prof)
    instrument.set_metrics(met)
    instrument.reset_metrics()


def _mlp():
    net = mx.sym.Variable('data')
    net = mx.sym.FullyConnected(net, num_hidden=32, name='fc1')
    net = mx.sym.Activation(net, act_type='relu', name='act1')
    net = mx.sym.FullyConnected(net, num_hidden=8, name='fc2')
    return mx.sym.SoftmaxOutput(net, name='softmax')


def _data(rows=128, rng_seed=0):
    rng = np.random.RandomState(rng_seed)
    X = rng.randn(rows, 16).astype(np.float32)
    Y = (rng.rand(rows) * 8).astype(np.float32)
    return X, Y


def _fit(mesh=None, partition=None, num_epoch=2, seed=7, env=None,
         kvstore='local', begin_epoch=0, module=None, **fit_kw):
    X, Y = _data()
    it = mx.io.NDArrayIter(X, Y, batch_size=32)
    saved = {}
    for k, v in (env or {}).items():
        saved[k] = os.environ.get(k)
        os.environ[k] = v
    try:
        mx.random.seed(seed)
        mod = module or mx.mod.Module(_mlp(), context=mx.cpu())
        mod.fit(it, num_epoch=num_epoch, optimizer='sgd',
                optimizer_params={'learning_rate': 0.1, 'momentum': 0.9},
                eval_metric='acc', initializer=mx.init.Uniform(0.05),
                mesh=mesh, partition=partition, kvstore=kvstore,
                begin_epoch=begin_epoch, **fit_kw)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return mod


def _params(mod):
    args, _ = mod.get_params()
    return {k: v.asnumpy() for k, v in args.items()}


# ---------------------------------------------------------------------------
# spec parsing / partition units (no fit)
# ---------------------------------------------------------------------------

def test_parse_mesh_spec_forms():
    from mxnet_tpu.parallel.mesh import parse_mesh_spec
    assert parse_mesh_spec('4x2') == {'dp': 4, 'tp': 2}
    assert parse_mesh_spec('8') == {'dp': 8, 'tp': 1}
    assert parse_mesh_spec(8) == {'dp': 8, 'tp': 1}
    assert parse_mesh_spec('dp=2,tp=4') == {'dp': 2, 'tp': 4}
    assert parse_mesh_spec('tp=2') == {'dp': 1, 'tp': 2}
    assert parse_mesh_spec((2, 2)) == {'dp': 2, 'tp': 2}
    assert parse_mesh_spec({'dp': 2}) == {'dp': 2, 'tp': 1}
    with pytest.raises(ValueError):
        parse_mesh_spec('pp=4')
    with pytest.raises(ValueError):
        parse_mesh_spec('')


def test_build_mesh_device_bound():
    from mxnet_tpu.parallel.mesh import build_dp_tp_mesh, mesh_sig
    mesh = build_dp_tp_mesh('4x2')
    assert mesh.shape == {'dp': 4, 'tp': 2}
    assert mesh_sig(mesh) == 'dp=4,tp=2'
    with pytest.raises(ValueError):
        build_dp_tp_mesh('16x2')   # only 8 virtual devices


def test_partition_and_zero_specs():
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel.mesh import build_dp_tp_mesh, partition_spec
    from mxnet_tpu.parallel.zero import zero_partition_spec
    mesh = build_dp_tp_mesh('4x2')
    # replicated policy: everything P()
    assert partition_spec((32, 16), mesh, 'replicated') == P()
    # auto: largest tp-divisible dim gets the tp axis
    assert partition_spec((32, 16), mesh, 'auto') == P('tp', None)
    assert partition_spec((8, 32), mesh, 'auto') == P(None, 'tp')
    # indivisible stays replicated instead of failing
    assert partition_spec((7, 5), mesh, 'auto') == P()
    # dict policy: first substring match wins
    spec = partition_spec((32, 16), mesh, {'fc1': ('tp', None)},
                          name='fc1_weight')
    assert spec == P('tp', None)
    # ZeRO composes with the param's tp placement on a free dim
    z = zero_partition_spec((32, 16), mesh, base=P('tp', None))
    assert z == P('tp', 'dp')
    # no dp-divisible free dim -> stays on the base spec
    assert zero_partition_spec((7, 5), mesh) == P()
    assert zero_partition_spec((32,), mesh) == P('dp')


# ---------------------------------------------------------------------------
# tentpole: sharded fit == single-device model
# ---------------------------------------------------------------------------

def test_sharded_fit_matches_single_device_oracle():
    oracle = _params(_fit())
    for partition in ('replicated', 'auto'):
        got = _fit(mesh='4x2', partition=partition)
        assert got._fused is not None, 'sharded fit left the fused path'
        sh = _params(got)
        for k in oracle:
            np.testing.assert_allclose(
                sh[k], oracle[k], rtol=2e-5, atol=2e-6,
                err_msg='%s diverged under %s' % (k, partition))


def test_zero_opt_state_is_dp_sharded():
    mod = _fit(mesh='4x2', partition='auto')
    assert mod._fused_shardings is not None
    sharded = 0
    for name, leaf in mod._fused_opt_state.items():
        spec = tuple(leaf.sharding.spec)
        if 'dp' in spec:
            sharded += 1
            # the committed shard really is 1/dp of the leaf
            shard_rows = [s.data.shape for s in leaf.addressable_shards]
            assert all(np.prod(r) <= np.prod(leaf.shape) // 4
                       for r in shard_rows)
    assert sharded > 0, 'no optimizer-state leaf was ZeRO-sharded'


def test_mesh_1x1_bit_for_bit():
    base = _fit()
    one = _fit(mesh='1x1')
    pb, po = _params(base), _params(one)
    for k in pb:
        assert np.array_equal(pb[k], po[k]), \
            '%s differs on the 1x1 mesh' % k
    # metric value identity over a deterministic score pass
    X, Y = _data()
    m1 = base.score(mx.io.NDArrayIter(X, Y, batch_size=32), 'acc')
    m2 = one.score(mx.io.NDArrayIter(X, Y, batch_size=32), 'acc')
    assert m1 == m2


def test_batch_not_divisible_by_dp_raises():
    X, Y = _data(rows=96)
    it = mx.io.NDArrayIter(X, Y, batch_size=36)   # 36 % 8 != 0
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    with pytest.raises((ValueError, MXNetError)):
        mod.fit(it, num_epoch=1, mesh='8', optimizer='sgd',
                initializer=mx.init.Uniform(0.05))


def test_mesh_and_context_list_exclusive():
    X, Y = _data()
    it = mx.io.NDArrayIter(X, Y, batch_size=32)
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(0), mx.cpu(1)])
    with pytest.raises(MXNetError):
        mod.fit(it, num_epoch=1, mesh='2x1', optimizer='sgd',
                initializer=mx.init.Uniform(0.05))


# ---------------------------------------------------------------------------
# ZeRO state round-trip through save_checkpoint / auto_resume
# ---------------------------------------------------------------------------

def test_zero_state_checkpoint_roundtrip(tmp_path):
    pfx = str(tmp_path / 'ck')
    oracle = _params(_fit(mesh='4x2', partition='auto', num_epoch=4))

    m1 = _fit(mesh='4x2', partition='auto', num_epoch=2)
    m1.save_checkpoint(pfx, 2, save_optimizer_states=True)

    m2 = mx.mod.Module.load(pfx, 2, load_optimizer_states=True)
    _fit(mesh='4x2', partition='auto', num_epoch=4, begin_epoch=2,
         module=m2, arg_params=m2._arg_params,
         aux_params=m2._aux_params)
    got = _params(m2)
    for k in oracle:
        assert np.array_equal(oracle[k], got[k]), \
            '%s lost momentum across the restart' % k
    # and the restored state went back onto its ZeRO shardings
    assert any('dp' in tuple(leaf.sharding.spec)
               for leaf in m2._fused_opt_state.values())


def test_auto_resume_restarts_sharded(tmp_path):
    pfx = str(tmp_path / 'ar')
    _fit(mesh='4x2', num_epoch=2, checkpoint_prefix=pfx)
    instrument.set_metrics(True)
    before = instrument.metrics_snapshot()['counters'] \
        .get('checkpoint.resumes', 0)
    mod = _fit(mesh='4x2', num_epoch=3, checkpoint_prefix=pfx,
               auto_resume=True)
    after = instrument.metrics_snapshot()['counters'] \
        .get('checkpoint.resumes', 0)
    assert after == before + 1
    assert mod._fused is not None


# ---------------------------------------------------------------------------
# perfwatch satellite: per-device vs global FLOPs under the mesh
# ---------------------------------------------------------------------------

def test_mfu_accounting_under_mesh():
    from mxnet_tpu import perfwatch
    mod = _fit(mesh='4x2', partition='auto',
               env={'MXTPU_PERFWATCH': '1'})
    try:
        g = instrument.metrics_snapshot()['gauges']
        assert g.get('perf.num_devices') == 8
        assert 0.0 <= g['perf.mfu'] <= 1.0
        rows = [r for r in perfwatch.executables()
                if r['kind'] == 'fit_step' and r.get('num_devices') == 8]
        assert rows, 'no mesh-partitioned fit_step row registered'
        row = rows[0]
        assert row['global_flops'] == row['flops'] * 8
        # perf.step_flops reports the GLOBAL model flops
        assert g['perf.step_flops'] == row['global_flops']
        stem = 'xla.fit_step[%s]' % row['key']
        assert g[stem + '.num_devices'] == 8
        assert g[stem + '.global_flops'] == row['global_flops']
    finally:
        perfwatch.set_enabled(False)
        perfwatch.refresh()


# ---------------------------------------------------------------------------
# kvstore demotion: control plane survives, data plane refuses
# ---------------------------------------------------------------------------

def test_dist_kvstore_demoted_under_mesh():
    instrument.set_metrics(True)
    mod = _fit(mesh='4x2', kvstore='dist_async', num_epoch=1)
    kv = mod._kvstore
    try:
        assert kv.control_plane_only
        assert mod._fused is not None, \
            'mesh fit fell off the fused path under a dist store'
        kv.barrier()          # control plane still live
        with pytest.raises(MXNetError):
            kv.push(0, mx.nd.array(np.zeros(3, np.float32)))
        with pytest.raises(MXNetError):
            kv.pull(0, out=mx.nd.array(np.zeros(3, np.float32)))
    finally:
        kv.close()


# ---------------------------------------------------------------------------
# warm start: AOT tables key on (batch_sig, mesh_sig)
# ---------------------------------------------------------------------------

def test_warm_sharded_fit_zero_hot_traces(tmp_path, monkeypatch):
    # a manifest WITHOUT installing the process-global persistent cache
    # (the test_perfwatch pattern — installing the cache would leak
    # into later knobs-off tests in the same process)
    from mxnet_tpu import compile_cache
    manifest = compile_cache._Manifest(str(tmp_path / 'manifest.json'))
    monkeypatch.setattr(compile_cache, '_manifest', manifest)
    instrument.set_metrics(True)
    _fit(mesh='4x2')                                # cold: records sigs
    before = instrument.metrics_snapshot()['counters']
    mod = _fit(mesh='4x2', env={'MXTPU_WARM_START': '1'})
    after = instrument.metrics_snapshot()['counters']
    hot = after.get('executor.xla_traces', 0) - \
        before.get('executor.xla_traces', 0)
    assert hot == 0, 'warm sharded fit traced on the hot path'
    assert after.get('compile.aot_calls', 0) > \
        before.get('compile.aot_calls', 0)
    assert mod._fused is not None
    # manifest entries carry the mesh sig — a different mesh must NOT
    # replay them
    entries = manifest.entries(kind='fit_step')
    assert entries and all(
        (t.get('meta') or {}).get('mesh') == 'dp=4,tp=2|replicated'
        for t in entries)


def test_sig_keys_are_mesh_qualified():
    from mxnet_tpu import compile_cache
    shapes = {'data': ((32, 16), 'float32')}
    assert compile_cache.sig_key(shapes) != \
        compile_cache.sig_key(shapes, mesh='dp=4,tp=2|auto')
    assert compile_cache.sig_key(shapes, mesh='a') != \
        compile_cache.sig_key(shapes, mesh='b')


def test_nonfused_fallback_with_demoted_store():
    """MXTPU_FUSED_FIT=0 + dist store + mesh: update() must treat the
    demoted store like no store (local updater), not crash into its
    refusing data plane."""
    mod = _fit(mesh='4x2', kvstore='dist_async', num_epoch=1,
               env={'MXTPU_FUSED_FIT': '0'})
    try:
        assert mod._fused is None
        assert mod._kvstore.control_plane_only
        a = _params(_fit(num_epoch=1, env={'MXTPU_FUSED_FIT': '0'}))
        b = _params(mod)
        for k in a:
            np.testing.assert_allclose(b[k], a[k], rtol=2e-5,
                                       atol=2e-6)
    finally:
        mod._kvstore.close()


def test_mesh_change_reinitializes_optimizer():
    """A fit without a mesh followed by a fit WITH one on the same
    module must re-derive the optimizer wiring — the dist store gets
    demoted instead of silently keeping its old data-plane role."""
    X, Y = _data()
    mod = _fit(num_epoch=1)                     # plain single-chip fit
    assert mod.optimizer_initialized
    mod2 = _fit(mesh='4x2', kvstore='dist_async', num_epoch=1,
                module=mod)
    try:
        assert mod2._kvstore is not None
        assert mod2._kvstore.control_plane_only
        assert mod2._fused is not None
    finally:
        mod2._kvstore.close()


def test_restored_states_colocate_on_mesh(tmp_path):
    """Updater.set_states output is device-0 committed; the first
    non-fused mesh update must re-place it against the sharded weight
    instead of raising a jit device conflict."""
    fname = str(tmp_path / 'opt.states')
    m1 = _fit(mesh='4x2', num_epoch=1, env={'MXTPU_FUSED_FIT': '0'})
    m1.save_optimizer_states(fname)
    m2 = _fit(mesh='4x2', num_epoch=1, env={'MXTPU_FUSED_FIT': '0'})
    m2.load_optimizer_states(fname)
    # one more epoch with the restored (host-pickled) state
    _fit(mesh='4x2', num_epoch=2, begin_epoch=1, module=m2,
         env={'MXTPU_FUSED_FIT': '0'},
         arg_params=m2.get_params()[0], aux_params=m2.get_params()[1])
    assert m2._fused is None


def test_fixed_params_aot_sharding_consistent():
    """Frozen (fixed) params are tp-sharded by the executor group under
    partition='auto'; the fused step's declared in_shardings must match
    so the AOT call path never hits a sharding mismatch (zero
    aot_fallbacks)."""
    X, Y = _data()
    it = mx.io.NDArrayIter(X, Y, batch_size=32)
    instrument.set_metrics(True)
    before = instrument.metrics_snapshot()['counters']
    mx.random.seed(7)
    mod = mx.mod.Module(_mlp(), context=mx.cpu(),
                        fixed_param_names=['fc1_weight', 'fc1_bias'])
    os.environ['MXTPU_PERFWATCH'] = '1'
    try:
        mod.fit(it, num_epoch=2, optimizer='sgd',
                optimizer_params={'learning_rate': 0.1,
                                  'momentum': 0.9},
                eval_metric='acc', initializer=mx.init.Uniform(0.05),
                mesh='4x2', partition='auto')
    finally:
        os.environ.pop('MXTPU_PERFWATCH', None)
        from mxnet_tpu import perfwatch
        perfwatch.set_enabled(False)
    assert mod._fused is not None
    after = instrument.metrics_snapshot()['counters']
    assert after.get('compile.aot_calls', 0) > \
        before.get('compile.aot_calls', 0)
    assert after.get('compile.aot_fallbacks', 0) == \
        before.get('compile.aot_fallbacks', 0)


def test_nonfused_fallback_trains_under_mesh():
    """MXTPU_FUSED_FIT=0 under a mesh: the legacy per-parameter updater
    loop runs on sharded arrays (Updater._colocate_state places fresh
    optimizer state where the weight lives) and matches the
    single-device loop."""
    a = _params(_fit(env={'MXTPU_FUSED_FIT': '0'}))
    mod = _fit(mesh='4x2', env={'MXTPU_FUSED_FIT': '0'})
    assert mod._fused is None
    b = _params(mod)
    for k in a:
        np.testing.assert_allclose(b[k], a[k], rtol=2e-5, atol=2e-6)


def test_gradient_arrays_under_a_mesh_are_made_where_their_weights_lie():
    """A bound training executor's gradient arrays reach the devices when
    first read (``ndarray.ZerosWhenRead``, PR 34).  The fused sharded fit
    never reads them; what does (``Module.update``'s per-parameter loop,
    ``Executor.backward`` under ``grad_req='add'``, ``get_input_grads``)
    finds zeros under the sharding the mesh gives that parameter."""
    from mxnet_tpu.ndarray import ZerosWhenRead
    mod = _fit(mesh='4x2', partition='auto', num_epoch=1)
    assert mod._fused is not None
    exec_ = mod._exec_group.execs[0]
    assert set(exec_.grad_dict) == set(mod._param_names)
    assert all(isinstance(g, ZerosWhenRead) and g._made is None
               for g in exec_.grad_dict.values())
    for name, grad in exec_.grad_dict.items():
        weight = exec_.arg_dict[name].handle
        assert grad.shape == weight.shape and not grad.asnumpy().any()
        assert grad.handle.sharding.is_equivalent_to(weight.sharding,
                                                     weight.ndim)
    # some weight is split over the two tensor-parallel devices: so were
    # its zeros, and not every array lies whole on every device
    assert any(len({s.index for s in g.handle.addressable_shards}) > 1
               for g in exec_.grad_dict.values())

    # accumulated into (grad_req 'add' reads the array it adds to): twice
    # one batch's gradient after two passes, under the mesh as without it
    def accumulated(mesh):
        module = mx.mod.Module(_mlp(), context=mx.cpu())
        if mesh:
            module._set_parallel(mesh, 'auto')
        module.bind([('data', (32, 16))], [('softmax_label', (32,))],
                    grad_req='add')
        mx.random.seed(7)
        module.init_params(mx.init.Uniform(0.05))
        X, Y = _data(32)
        batch = mx.io.DataBatch([mx.nd.array(X)], [mx.nd.array(Y)])
        grads = module._exec_group.execs[0].grad_dict
        assert all(g._made is None for g in grads.values())
        for _ in range(2):
            module.forward(batch, is_train=True)
            module.backward()
        return {k: v.asnumpy() for k, v in grads.items()}
    plain, sharded = accumulated(None), accumulated('4x2')
    assert np.abs(plain['fc1_weight']).max() > 0
    for name in plain:
        np.testing.assert_allclose(sharded[name], plain[name], rtol=2e-5,
                                   atol=2e-6)


# ---------------------------------------------------------------------------
# bucketing: every bucket module inherits the mesh plan
# ---------------------------------------------------------------------------

def test_bucketing_module_sharded_parity():
    from mxnet_tpu import ndarray as nd
    from mxnet_tpu import symbol as sym

    def sym_gen(seq_len):
        data = sym.Variable('data')
        emb = sym.Embedding(data, input_dim=16, output_dim=8,
                            name='embed')
        pooled = sym.mean(emb, axis=1)
        fc = sym.FullyConnected(pooled, num_hidden=4, name='fc')
        return (sym.SoftmaxOutput(fc, name='softmax'),
                ['data'], ['softmax_label'])

    def run(mesh):
        mx.random.seed(3)
        mod = mx.module.BucketingModule(sym_gen, default_bucket_key=8,
                                        context=mx.cpu())
        if mesh:
            mod._set_parallel(mesh)
        mod.bind(data_shapes=[('data', (8, 8))],
                 label_shapes=[('softmax_label', (8,))])
        mod.init_params(initializer=mx.init.Uniform(0.1))
        mod.init_optimizer(optimizer='sgd',
                           optimizer_params={'learning_rate': 0.1,
                                             'momentum': 0.9})
        rngb = np.random.RandomState(0)
        for step in range(6):
            seq = [8, 4, 8][step % 3]
            batch = mx.io.DataBatch(
                [nd.array(rngb.randint(0, 16, (8, seq))
                          .astype(np.float32))],
                [nd.array(rngb.randint(0, 4, 8).astype(np.float32))],
                bucket_key=seq,
                provide_data=[('data', (8, seq))],
                provide_label=[('softmax_label', (8,))])
            mod._fit_step(batch)
        arg, _ = mod.get_params()
        assert any(m._fused is not None for m in mod._buckets.values())
        if mesh:
            # every bound bucket carries the plan (per-bucket sharded
            # precompile rides the ordinary warm-start hook)
            assert all(m._mesh_plan is not None
                       for m in mod._buckets.values())
        return {k: v.asnumpy() for k, v in arg.items()}

    a = run(None)
    b = run('4x2')
    for k in a:
        np.testing.assert_allclose(b[k], a[k], rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# the hermetic acceptance tool itself
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_check_multichip_e2e(tmp_path):
    """The full 8-virtual-device subprocess smoke (oracle parity, 1x1
    identity, warm zero-trace, MFU bounds) — slow: four child
    interpreters."""
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, 'tools',
                                      'check_multichip.py'),
         '--dir', str(tmp_path / 'mc')],
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
