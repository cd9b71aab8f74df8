"""Step-compiler pass pipeline (fuse.py PassManager): per-pass oracle
parity on a small conv+BN+FC model, pass-stat counter pins, knob
semantics (off == byte-identical, skip lists, legacy mapping), and the
knobs-off zero-surface guard (the PR-7/9/10 <2x floor contract)."""
import os
import subprocess
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import sym, fuse, config, instrument
from mxnet_tpu.executor import _build_graph_fn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _net():
    """Small conv+BN+FC model on which EVERY pass has a target."""
    data = sym.Variable('data')
    c0 = sym.Convolution(data, num_filter=6, kernel=(3, 3), pad=(1, 1),
                         no_bias=True, name='c0')
    b0 = sym.BatchNorm(c0, fix_gamma=False, use_global_stats=True,
                       name='b0')
    a0 = sym.Activation(b0, act_type='relu', name='a0')
    b1 = sym.BatchNorm(a0, fix_gamma=False, name='b1')
    a1 = sym.Activation(b1, act_type='relu', name='a1')
    c1 = sym.Convolution(a1, num_filter=8, kernel=(1, 1), no_bias=True,
                         name='c1')
    b2 = sym.BatchNorm(c1, fix_gamma=False, output_mean_var=True,
                       name='b2')
    a2 = sym.Activation(b2[0], act_type='relu', name='a2')
    p = sym.Pooling(a2, global_pool=True, kernel=(2, 2),
                    pool_type='avg')
    f = sym.Flatten(p)
    fc = sym.FullyConnected(f, num_hidden=10, no_bias=True, name='fc')
    addb = sym.broadcast_add(fc, sym.Variable('fc_epi_bias'),
                             name='addb')
    r = sym.Activation(addb, act_type='relu', name='fc_relu')
    konst = sym._full(shape=(1, 10), value=0.25, name='konst')
    out = sym.broadcast_add(r, konst, name='plus_const')
    return sym.SoftmaxOutput(out, name='softmax')


def _values(net, seed=0):
    dshape = (4, 3, 8, 8)
    shapes = net.infer_shape(data=dshape, fc_epi_bias=(10,))
    rng = np.random.RandomState(seed)
    vals = {}
    for n, s in zip(net.list_arguments(), shapes[0]):
        if n.endswith('_gamma'):
            vals[n] = jnp.asarray((rng.rand(*s) + 0.5).astype(np.float32))
        else:
            vals[n] = jnp.asarray((rng.randn(*s) * 0.3).astype(np.float32))
    vals['data'] = jnp.asarray(rng.rand(*dshape).astype(np.float32))
    vals['softmax_label'] = jnp.asarray(
        rng.randint(0, 10, 4).astype(np.float32))
    aux = {n: (jnp.ones(s) if 'var' in n else
               jnp.asarray((rng.randn(*s) * 0.1).astype(np.float32)))
           for n, s in zip(net.list_auxiliary_states(), shapes[2])}
    return vals, aux


_PASS_LEVELS = {'constant_fold': 'safe', 'dead_branch': 'safe',
                'conv_bn_fold': 'aggressive',
                'bn_relu_conv': 'aggressive', 'bn_relu': 'aggressive',
                'epilogue': 'safe', 'nhwc_regions': 'aggressive'}


def test_pass_table_pinned():
    passes = fuse.default_passes()
    assert [p.name for p in passes] == list(_PASS_LEVELS)
    for p in passes:
        assert p.level == _PASS_LEVELS[p.name], p.name


def _run_pipeline(net, is_train, mode, only=None, live_kernels=False,
                  monkeypatch=None):
    if live_kernels:
        monkeypatch.setattr(fuse, '_kernel_paths_live', lambda: True)
    skip = () if only is None else tuple(
        n for n in _PASS_LEVELS if n != only)
    mgr = fuse.PassManager()
    out = mgr.run(net, is_train, mode, skip=skip)
    return out, mgr.last_stats


@pytest.mark.parametrize('name', sorted(_PASS_LEVELS))
def test_per_pass_oracle_parity(name, monkeypatch):
    """Each pass alone: forward outputs, aux updates and gradients of
    the rewritten graph match the unfused oracle — bit-for-bit for
    safe passes, rtol 1e-5 for the folding/kernel passes."""
    net = _net()
    vals, aux = _values(net)
    key = jax.random.PRNGKey(0)
    level = _PASS_LEVELS[name]
    fused, stats = _run_pipeline(net, True, level, only=name,
                                 live_kernels=True,
                                 monkeypatch=monkeypatch)
    if name != 'nhwc_regions':   # layout planning needs bn_relu_conv
        assert stats['passes'][name]['rewrites'] > 0, \
            '%s did not rewrite the model: %s' % (name, stats)

    o0, a0 = _build_graph_fn(net, True)(vals, aux, key)
    o1, a1 = _build_graph_fn(fused, True)(vals, aux, key)
    if level == 'safe':
        assert np.array_equal(np.asarray(o0[0]), np.asarray(o1[0])), \
            'safe pass %s not bit-for-bit' % name
    else:
        np.testing.assert_allclose(np.asarray(o0[0]),
                                   np.asarray(o1[0]),
                                   rtol=1e-5, atol=1e-6)
    assert set(a0) == set(a1)
    for k in a0:
        np.testing.assert_allclose(np.asarray(a0[k]), np.asarray(a1[k]),
                                   rtol=1e-5, atol=1e-6, err_msg=k)

    grad_keys = [k for k in vals if k not in ('data', 'softmax_label')]

    def make_loss(s):
        f = _build_graph_fn(s, True)

        def loss(p):
            merged = dict(vals)
            merged.update(p)
            outs, _ = f(merged, aux, key)
            lab = jax.nn.one_hot(
                vals['softmax_label'].astype(jnp.int32), 10)
            return -jnp.mean(jnp.sum(
                lab * jnp.log(outs[0] + 1e-9), axis=1))
        return loss

    p = {k: vals[k] for k in grad_keys}
    g0 = jax.grad(make_loss(net))(p)
    g1 = jax.grad(make_loss(fused))(p)
    for k in grad_keys:
        np.testing.assert_allclose(np.asarray(g0[k]), np.asarray(g1[k]),
                                   rtol=1e-4, atol=1e-5, err_msg=k)


def test_full_pipeline_trains_to_parity(monkeypatch):
    """MXTPU_FUSE=aggressive through make_train_step: parameters after
    3 fused steps track the unfused run to rtol 1e-5 (the whole-
    pipeline folding contract)."""
    from mxnet_tpu.parallel.train_step import (make_train_step,
                                               make_sgd_momentum,
                                               sgd_momentum_init)
    net = _net()
    vals, aux = _values(net)
    params0 = {k: v for k, v in vals.items()
               if k not in ('data', 'softmax_label')}
    batch = {'data': vals['data'],
             'softmax_label': vals['softmax_label']}
    opt = make_sgd_momentum(lr=0.1, momentum=0.9, wd=0.0,
                            rescale_grad=0.25)
    key = jax.random.PRNGKey(0)
    results = {}
    for mode in ('off', 'safe', 'aggressive'):
        monkeypatch.setenv('MXTPU_FUSE', mode)
        step = make_train_step(net, opt, ('data', 'softmax_label'),
                               donate=False)
        p, a, s = dict(params0), dict(aux), sgd_momentum_init(params0)
        for _ in range(3):
            _, p, a, s = step(p, a, s, batch, key)
        results[mode] = {k: np.asarray(v) for k, v in p.items()}
    for k in results['off']:
        # safe passes replay identical ops: bit-for-bit
        assert np.array_equal(results['off'][k], results['safe'][k]), k
        np.testing.assert_allclose(results['off'][k],
                                   results['aggressive'][k],
                                   rtol=1e-5, atol=1e-6, err_msg=k)


def test_pass_counters_pinned(monkeypatch):
    """fuse.pass.<name>.rewrites counters carry the per-pass stats
    through the instrument registry (the perfwatch reporting leg)."""
    instrument.set_metrics(True)
    try:
        monkeypatch.setattr(fuse, '_kernel_paths_live', lambda: True)
        before = dict(instrument.metrics_snapshot()['counters'])
        mgr = fuse.PassManager()
        mgr.run(_net(), True, 'aggressive')
        stats = mgr.last_stats
        assert stats['mode'] == 'aggressive'
        fired = {k: v['rewrites'] for k, v in stats['passes'].items()
                 if v['rewrites']}
        assert set(fired) >= {'constant_fold', 'dead_branch',
                              'conv_bn_fold', 'bn_relu_conv',
                              'bn_relu', 'epilogue'}, fired
        after = instrument.metrics_snapshot()['counters']
        for name, n in fired.items():
            cname = 'fuse.pass.%s.rewrites' % name
            assert after.get(cname, 0) - before.get(cname, 0) == n, \
                cname
        assert after.get('fuse.runs', 0) > before.get('fuse.runs', 0)
    finally:
        instrument.set_metrics(False)


def test_mode_knob_semantics(monkeypatch):
    monkeypatch.delenv('MXTPU_FUSE', raising=False)
    assert fuse.fuse_mode() == 'off'
    monkeypatch.setenv('MXTPU_FUSE', 'safe')
    assert fuse.fuse_mode() == 'safe'
    monkeypatch.setenv('MXTPU_FUSE', 'bogus')
    with pytest.raises(ValueError):
        fuse.fuse_mode()


def test_off_returns_same_object(monkeypatch):
    """MXTPU_FUSE=off is ZERO graph surface: the pipeline hands back
    the input symbol object itself (byte-identical program
    downstream; tools/check_fusion.py pins the HLO equality)."""
    monkeypatch.setenv('MXTPU_FUSE', 'off')
    net = _net()
    assert fuse.apply_fuse_passes(net, True) is net
    assert fuse.apply_fuse_passes(net, False) is net


def test_skip_knob(monkeypatch):
    monkeypatch.setenv('MXTPU_FUSE', 'safe')
    monkeypatch.setenv('MXTPU_FUSE_SKIP',
                       'constant_fold,dead_branch,epilogue')
    net = _net()
    assert fuse.apply_fuse_passes(net, True) is net  # everything skipped
    monkeypatch.setenv('MXTPU_FUSE_SKIP', 'constant_fold,dead_branch')
    fused = fuse.apply_fuse_passes(net, True)
    ops = [n.op for n in fused.topo_nodes() if not n.is_variable]
    assert '_fused_epilogue' in ops and '_graph_constant' not in ops


def test_kernel_gated_passes_step_aside_on_reference(monkeypatch):
    """On the jnp reference path (no TPU, no interpret) the kernel-
    lowered rewrites must not fire: their fallback forms materialize
    traffic XLA would have fused (the measured +13% bytes)."""
    monkeypatch.delenv('MXTPU_FORCE_PALLAS_INTERPRET', raising=False)
    monkeypatch.delenv('MXTPU_ASSUME_TPU', raising=False)
    mgr = fuse.PassManager()
    fused = mgr.run(_net(), True, 'aggressive')
    stats = mgr.last_stats
    assert stats['passes']['bn_relu_conv']['rewrites'] == 0
    assert stats['passes']['nhwc_regions']['rewrites'] == 0
    ops = [n.op for n in fused.topo_nodes() if not n.is_variable]
    assert '_bn_relu_conv' not in ops
    # the algebraic/structural passes still fire
    assert '_conv_bn_folded' in ops and '_bn_relu' in ops


def test_executor_program_path_uses_pipeline(monkeypatch):
    """Executor.forward compiles the rewritten program under the knob
    and matches the knob-off executor's outputs."""
    net = _net()
    vals, aux = _values(net)
    outs = {}
    for mode in ('off', 'aggressive'):
        monkeypatch.setenv('MXTPU_FUSE', mode)
        exe = net.bind(mx.cpu(),
                       {k: mx.nd.array(np.asarray(v))
                        for k, v in vals.items()},
                       aux_states={k: mx.nd.array(np.asarray(v))
                                   for k, v in aux.items()})
        outs[mode] = exe.forward(is_train=False)[0].asnumpy()
        fused_sym = exe._program_symbol(False)
        if mode == 'off':
            assert fused_sym is exe._symbol
        else:
            assert '_conv_bn_folded' in [
                n.op for n in fused_sym.topo_nodes()
                if not n.is_variable]
    np.testing.assert_allclose(outs['off'], outs['aggressive'],
                               rtol=1e-5, atol=1e-6)


def test_constant_fold_caps_size():
    """Constants above _CONST_FOLD_MAX_ELEMS stay symbolic — XLA
    inlines literals into the program."""
    big = sym._full(shape=(512, 512), value=1.0, name='big')  # 256k els
    out = sym.broadcast_add(sym.Variable('x'), big)
    net = sym.make_loss(out, name='loss')
    folded, n = fuse.fold_constants(net, True)
    assert n == 0 and folded is net


def test_dead_branch_prunes_unused_mean_var():
    d = sym.Variable('data')
    bn = sym.BatchNorm(d, output_mean_var=True, name='bn')
    net = sym.make_loss(bn[0], name='loss')
    pruned, n = fuse.prune_dead_branches(net, True)
    assert n == 1
    bn_node = [x for x in pruned.topo_nodes() if x.op == 'BatchNorm'][0]
    assert not bn_node.attrs['output_mean_var']
    # consumed heads must survive
    net2 = sym.Group([sym.make_loss(bn[0], name='l0'), bn[1]])
    _, n2 = fuse.prune_dead_branches(net2, True)
    assert n2 == 0


def test_fold_conv_bn_training_gate():
    """Training-mode fold applies ONLY to frozen-stats BNs."""
    d = sym.Variable('data')
    c = sym.Convolution(d, num_filter=4, kernel=(1, 1), no_bias=True,
                        name='c')
    live = sym.BatchNorm(c, name='bn_live')
    net = sym.make_loss(live, name='loss')
    _, n = fuse.fold_conv_bn(net, is_train=True)
    assert n == 0                        # live batch stats: untouched
    _, n = fuse.fold_conv_bn(net, is_train=False)
    assert n == 1                        # inference folds it
    frozen = sym.BatchNorm(c, use_global_stats=True, name='bn_frozen')
    net2 = sym.make_loss(frozen, name='loss2')
    _, n = fuse.fold_conv_bn(net2, is_train=True)
    assert n == 1                        # frozen stats fold in training


def test_epilogue_multi_consumer_blocks_fold():
    """A producer consumed OUTSIDE the chain must not fold (folding
    would recompute it); a chain whose TAIL is multi-consumer still
    folds up to the tail (the fused output feeds both reads)."""
    d = sym.Variable('data')
    fc = sym.FullyConnected(d, num_hidden=4, no_bias=True, name='fc')
    r = sym.Activation(fc, act_type='relu', name='r')
    # fc consumed by the relu AND directly: no chain from fc
    out = r + fc
    net = sym.make_loss(out, name='loss')
    fused, n = fuse.fuse_epilogues(net, True)
    ops = [x.op for x in fused.topo_nodes() if not x.is_variable]
    assert '_fused_epilogue' not in ops and n == 0
    # tail read twice: still one fused node, no recompute
    net2 = sym.make_loss(r + r, name='loss2')
    fused2, n2 = fuse.fuse_epilogues(net2, True)
    ops2 = [x.op for x in fused2.topo_nodes() if not x.is_variable]
    assert ops2.count('_fused_epilogue') == 1 and n2 == 1


def test_skip_unknown_pass_raises(monkeypatch):
    """A typo'd MXTPU_FUSE_SKIP name must raise loudly (same policy as
    fuse_mode) — a skip that silently leaves the pass enabled poisons
    a bisection."""
    monkeypatch.setenv('MXTPU_FUSE', 'safe')
    monkeypatch.setenv('MXTPU_FUSE_SKIP', 'epilog')   # typo
    with pytest.raises(ValueError, match='epilog'):
        fuse.apply_fuse_passes(_net(), True)


def _fc_clip_net(double_clip=False):
    d = sym.Variable('data')
    fc = sym.FullyConnected(d, num_hidden=8, name='fc')
    r = sym.Activation(fc, act_type='relu', name='r')
    c = sym.clip(r, a_min=-1.0, a_max=0.5, name='cl')
    if double_clip:
        c = sym.clip(c, a_min=0.0, a_max=0.4, name='cl2')
    return sym.make_loss(c, name='loss')


def test_epilogue_safe_mode_never_kernel_lowers(monkeypatch):
    """Safe mode must keep the bit-exact replay even when the kernel
    paths are live — the blocked fp32 accumulation of
    fused_dot_epilogue reorders the K sum."""
    net = _fc_clip_net()
    rng = np.random.RandomState(3)
    vals = {'data': jnp.asarray(rng.randn(64, 32).astype(np.float32)),
            'fc_weight': jnp.asarray(
                rng.randn(8, 32).astype(np.float32) * 0.3),
            'fc_bias': jnp.asarray(rng.randn(8).astype(np.float32))}
    key = jax.random.PRNGKey(0)
    o_ref, _ = _build_graph_fn(net, True)(vals, {}, key)
    for mode, expect_lower in (('safe', False), ('aggressive', True)):
        fused, _ = _run_pipeline(net, True, mode, only='epilogue')
        node = [x for x in fused.topo_nodes()
                if x.op == '_fused_epilogue'][0]
        assert node.attrs.get('lower_kernel', False) is expect_lower
        monkeypatch.setenv('MXTPU_FORCE_PALLAS_INTERPRET', '1')
        o_f, _ = _build_graph_fn(fused, True)(vals, {}, key)
        monkeypatch.delenv('MXTPU_FORCE_PALLAS_INTERPRET')
        if expect_lower:
            np.testing.assert_allclose(np.asarray(o_ref[0]),
                                       np.asarray(o_f[0]),
                                       rtol=1e-5, atol=1e-6)
        else:
            assert np.array_equal(np.asarray(o_ref[0]),
                                  np.asarray(o_f[0])), \
                'safe epilogue took the kernel lowering'


def test_epilogue_double_clip_keeps_exact_replay(monkeypatch):
    """FC -> clip -> clip: the kernel lowering cannot express two
    clips, so even aggressive+interpret must fall back to the exact
    replay instead of dropping one (regression: the second clip
    silently overwrote the first)."""
    net = _fc_clip_net(double_clip=True)
    rng = np.random.RandomState(4)
    vals = {'data': jnp.asarray(rng.randn(64, 32).astype(np.float32)),
            'fc_weight': jnp.asarray(
                rng.randn(8, 32).astype(np.float32) * 0.5),
            'fc_bias': jnp.asarray(rng.randn(8).astype(np.float32))}
    key = jax.random.PRNGKey(0)
    o_ref, _ = _build_graph_fn(net, True)(vals, {}, key)
    fused, stats = _run_pipeline(net, True, 'aggressive',
                                 only='epilogue')
    assert stats['passes']['epilogue']['rewrites'] == 1
    monkeypatch.setenv('MXTPU_FORCE_PALLAS_INTERPRET', '1')
    o_f, _ = _build_graph_fn(fused, True)(vals, {}, key)
    monkeypatch.delenv('MXTPU_FORCE_PALLAS_INTERPRET')
    assert np.array_equal(np.asarray(o_ref[0]), np.asarray(o_f[0]))


def test_check_fusion_smoke():
    """The hermetic acceptance tool itself (tier-1): all passes fire,
    cost_analysis bytes drop >= 10%, oracle parity, off == unfused."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools',
                                      'check_fusion.py')],
        capture_output=True, text=True, timeout=900,
        env={k: v for k, v in os.environ.items()
             if not k.startswith('MXTPU_')})
    assert out.returncode == 0, out.stdout + out.stderr
    assert 'check_fusion: OK' in out.stdout


# ---------------------------------------------------------------------------
# Off-path overhead guard (the PR-7/9/10 <2x floor contract)
# ---------------------------------------------------------------------------

def _floor_hook():
    """The inlined ideal off path: the one knob read fuse_mode()
    cannot avoid."""
    if not str(config.get('MXTPU_FUSE') or '').strip().lower():
        return None


def test_knobs_off_zero_surface_guard(monkeypatch):
    """With the knob unset apply_fuse_passes must stay knob-read
    cheap (< 2x the inlined one-env-read floor) and return the input
    object — program-build sites pay nothing for the pipeline's
    existence."""
    monkeypatch.delenv('MXTPU_FUSE', raising=False)
    net = _net()
    assert fuse.apply_fuse_passes(net, True) is net
    n = 5000

    def measure(fn):
        best = float('inf')
        for _ in range(7):
            t0 = time.perf_counter()
            for _ in range(n):
                fn()
            best = min(best, time.perf_counter() - t0)
        return best

    ratio = min(
        (measure(lambda: fuse.apply_fuse_passes(net, True)) + 0.0)
        / max(measure(_floor_hook), 1e-9)
        for _ in range(3))          # best-of-3 damps noise
    assert ratio < 2.0, \
        'knobs-off apply_fuse_passes is %.2fx its floor' % ratio
