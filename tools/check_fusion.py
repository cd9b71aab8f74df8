#!/usr/bin/env python
"""Step-compiler smoke — the acceptance gate of the fuse.py pass
pipeline (hermetic: the parent never imports jax; the child pins its
own CPU backend).

One reference conv+BN+FC model crafted to exercise EVERY pass, one
child process, five assertions:

1. **Passes fire** — under ``MXTPU_FUSE=aggressive`` every pass in
   ``fuse.default_passes()`` reports ``rewrites > 0`` on the model
   (``fuse.last_run_stats``), and the ``fuse.pass.*`` counters carry
   the same numbers through the instrument registry.
2. **Cost drops** — the registered fused-step executable's
   ``cost_analysis`` under ``aggressive`` shows ``bytes accessed``
   strictly down (>= ``--min-bytes-drop``, default 10%) and flops not
   up vs ``off``, published as the ``fuse.cost.*`` delta gauges
   (``perfwatch.fuse_cost_delta``).
3. **Oracle parity** — training the model a few fused steps:
   ``safe`` matches ``off`` bit-for-bit (every param, byte-identical),
   ``aggressive`` to rtol 1e-5.
4. **off == pre-PR** — the ``MXTPU_FUSE=off`` lowered step's HLO text
   is byte-identical to the pipeline-bypassed program (the regression
   pin for "off really means unfused").
5. **Exposition** — the Prometheus text rendering carries ``fuse.*``
   series.

Usage: ``python tools/check_fusion.py``.  Exits nonzero on any failed
assertion.  CPU-safe; run by
``tests/test_fuse_passes.py`` under tier-1 and by hand after touching
fuse.py, the Pallas kernel library, or the executor's program paths.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)

BATCH = 8


# ---------------------------------------------------------------------------
# child
# ---------------------------------------------------------------------------

def _build_model():
    """conv+BN+FC reference model exercising every pass: a post-norm
    stem on frozen stats (conv_bn_fold, in training too), a pre-act
    residual block (bn_relu_conv + nhwc_regions), a leftover BN->relu
    (bn_relu), an unused mean/var head (dead_branch), a constant
    subgraph (constant_fold), and a bias-add/relu FC head
    (epilogue)."""
    from mxnet_tpu import sym
    data = sym.Variable('data')
    c0 = sym.Convolution(data, num_filter=8, kernel=(3, 3), pad=(1, 1),
                         no_bias=True, name='c0')
    b0 = sym.BatchNorm(c0, fix_gamma=False, use_global_stats=True,
                       name='b0')
    a0 = sym.Activation(b0, act_type='relu', name='a0')
    # pre-act block with projection shortcut: both convs fuse, the
    # residual add + following relu grow the NHWC region
    b1 = sym.BatchNorm(a0, fix_gamma=False, name='b1')
    a1 = sym.Activation(b1, act_type='relu', name='a1')
    c1 = sym.Convolution(a1, num_filter=8, kernel=(3, 3), pad=(1, 1),
                         no_bias=True, name='c1')
    sc = sym.Convolution(a1, num_filter=8, kernel=(1, 1), no_bias=True,
                         name='sc')
    res = c1 + sc
    a2 = sym.Activation(res, act_type='relu', name='a2')
    # leftover BN->relu (feeds pooling, not a fusable conv) with a
    # dead mean/var head
    b2 = sym.BatchNorm(a2, fix_gamma=False, output_mean_var=True,
                       name='b2')
    a3 = sym.Activation(b2[0], act_type='relu', name='a3')
    p = sym.Pooling(a3, global_pool=True, kernel=(2, 2),
                    pool_type='avg', name='pool')
    f = sym.Flatten(p, name='flat')
    # epilogue chain: FC(no_bias) -> +bias -> relu
    fc = sym.FullyConnected(f, num_hidden=16, no_bias=True, name='fc')
    fc_bias = sym.Variable('fc_epi_bias')
    addb = sym.broadcast_add(fc, fc_bias, name='addb')
    r = sym.Activation(addb, act_type='relu', name='fc_relu')
    # constant subgraph: _full -> broadcast_add pre-evaluates
    konst = sym._full(shape=(1, 16), value=0.25, name='konst')
    out = sym.broadcast_add(r, konst, name='plus_const')
    return sym.SoftmaxOutput(out, name='softmax')


def _init_values(net, seed=0):
    import numpy as np
    import jax.numpy as jnp
    dshape = (BATCH, 4, 16, 16)
    kwargs = {'data': dshape, 'fc_epi_bias': (16,)}
    arg_shapes, _, aux_shapes = net.infer_shape(**kwargs)
    rng = np.random.RandomState(seed)
    vals = {}
    for n, s in zip(net.list_arguments(), arg_shapes):
        if n.endswith('_gamma'):
            vals[n] = jnp.asarray(
                (rng.rand(*s) + 0.5).astype(np.float32))
        else:
            vals[n] = jnp.asarray(
                (rng.randn(*s) * 0.3).astype(np.float32))
    vals['data'] = jnp.asarray(rng.rand(*dshape).astype(np.float32))
    vals['softmax_label'] = jnp.asarray(
        rng.randint(0, 16, BATCH).astype(np.float32))
    aux = {}
    for n, s in zip(net.list_auxiliary_states(), aux_shapes):
        aux[n] = jnp.ones(s) if 'var' in n else \
            jnp.asarray((rng.randn(*s) * 0.1).astype(np.float32))
    return vals, aux


def _raw_step(net, mode):
    """The fused fit step (raw, un-jitted) with the pipeline pinned to
    ``mode`` — the exact program make_fit_step would jit."""
    import jax.numpy as jnp
    from mxnet_tpu.fuse import apply_fuse_passes
    from mxnet_tpu.parallel.train_step import (make_fit_step,
                                               make_sgd_momentum,
                                               _PlainUpdate)
    os.environ['MXTPU_FUSE'] = mode
    try:
        raw = make_fit_step(net, _PlainUpdate(make_sgd_momentum(
            lr=0.05, momentum=0.9, wd=0.0, rescale_grad=1.0 / BATCH)),
            data_names=(), _raw=True)
    finally:
        os.environ.pop('MXTPU_FUSE', None)

    def step(params, aux, opt_state, batch, rng):
        return raw(params, {}, aux, opt_state, batch,
                   jnp.float32(0.0), rng)
    return step


def _lower_step(net, mode, vals, aux):
    """jit-lower + compile the mode's step at the reference shapes;
    returns (compiled, hlo_text)."""
    import jax
    step = _raw_step(net, mode)
    params = {k: v for k, v in vals.items()
              if k not in ('data', 'softmax_label')}
    opt = {k: jax.numpy.zeros_like(v) for k, v in params.items()}
    batch = {'data': vals['data'],
             'softmax_label': vals['softmax_label']}
    lowered = jax.jit(step).lower(params, aux, opt, batch,
                                  jax.random.PRNGKey(0))
    return lowered.compile(), lowered.as_text()


def _train(net, mode, vals, aux, steps=4):
    import jax
    import numpy as np
    step = jax.jit(_raw_step(net, mode))
    params = {k: v for k, v in vals.items()
              if k not in ('data', 'softmax_label')}
    opt = {k: jax.numpy.zeros_like(v) for k, v in params.items()}
    a = dict(aux)
    batch = {'data': vals['data'],
             'softmax_label': vals['softmax_label']}
    key = jax.random.PRNGKey(0)
    for _ in range(steps):
        _, params, a, opt = step(params, a, opt, batch, key)
    return ({k: np.asarray(v) for k, v in params.items()},
            {k: np.asarray(v) for k, v in a.items()})


def _child(min_bytes_drop):
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import numpy as np
    sys.path.insert(0, _REPO)
    from mxnet_tpu import fuse, instrument, perfwatch

    instrument.set_metrics(True)
    net = _build_model()
    vals, aux = _init_values(net)

    # -- 1: every pass fires ------------------------------------------------
    # the kernel-lowered passes (bn_relu_conv, nhwc_regions) only
    # rewrite when the Pallas kernel paths compile — force the
    # interpreter so all seven fire on this CPU host
    os.environ['MXTPU_FORCE_PALLAS_INTERPRET'] = '1'
    try:
        fused = fuse.apply_fuse_passes(net, True, mode='aggressive')
    finally:
        os.environ.pop('MXTPU_FORCE_PALLAS_INTERPRET', None)
    stats = fuse.last_run_stats()
    assert stats['mode'] == 'aggressive', stats
    for p in fuse.default_passes():
        st = stats['passes'].get(p.name)
        assert st and st['rewrites'] > 0, \
            'pass %r did not fire on the reference model: %s' \
            % (p.name, stats['passes'])
    snap = instrument.metrics_snapshot()
    for p in fuse.default_passes():
        cname = 'fuse.pass.%s.rewrites' % p.name
        assert snap['counters'].get(cname, 0) >= \
            stats['passes'][p.name]['rewrites'], \
            'counter %s missing from the registry' % cname
    ops = [n.op for n in fused.topo_nodes() if not n.is_variable]
    for want in ('_conv_bn_folded', '_bn_relu_conv', '_bn_relu',
                 '_fused_epilogue', '_graph_constant'):
        assert want in ops, (want, ops)
    print('check_fusion: all %d passes fired %s'
          % (len(fuse.default_passes()),
             {k: v['rewrites'] for k, v in stats['passes'].items()}))

    # the kernel-path graph (interpret mode: real kernels through the
    # Pallas interpreter) must match the unfused forward to rtol
    import jax as _jax
    from mxnet_tpu.executor import _build_graph_fn
    key = _jax.random.PRNGKey(0)
    o_ref, _ = _build_graph_fn(net, True)(vals, aux, key)
    os.environ['MXTPU_FORCE_PALLAS_INTERPRET'] = '1'
    try:
        o_k, _ = _build_graph_fn(fused, True)(vals, aux, key)
    finally:
        os.environ.pop('MXTPU_FORCE_PALLAS_INTERPRET', None)
    np.testing.assert_allclose(np.asarray(o_ref[0]), np.asarray(o_k[0]),
                               rtol=1e-4, atol=1e-5)
    print('check_fusion: kernel-path (interpret) forward parity holds')

    # -- 2: cost_analysis drop ---------------------------------------------
    comp_off, hlo_off = _lower_step(net, 'off', vals, aux)
    comp_aggr, _ = _lower_step(net, 'aggressive', vals, aux)
    row_off = perfwatch.register_executable('fit_step_off', 'ref',
                                            comp_off)
    row_aggr = perfwatch.register_executable('fit_step_fused', 'ref',
                                             comp_aggr)
    assert row_off and row_off['bytes_accessed'] > 0, \
        'cost_analysis reported no bytes on this backend'
    delta = perfwatch.fuse_cost_delta(row_off, row_aggr)
    drop = delta['bytes_delta'] / row_off['bytes_accessed']
    print('check_fusion: bytes accessed %.3e -> %.3e (%.1f%% drop), '
          'flops %.3e -> %.3e'
          % (row_off['bytes_accessed'], row_aggr['bytes_accessed'],
             100 * drop, row_off['flops'], row_aggr['flops']))
    assert drop >= min_bytes_drop, \
        'aggressive dropped only %.1f%% of bytes accessed ' \
        '(need >= %.0f%%)' % (100 * drop, 100 * min_bytes_drop)
    assert row_aggr['flops'] <= row_off['flops'] * 1.001, \
        'aggressive INCREASED flops: %s -> %s' \
        % (row_off['flops'], row_aggr['flops'])

    # -- 3: oracle parity ---------------------------------------------------
    p_off, a_off = _train(net, 'off', vals, aux)
    p_safe, a_safe = _train(net, 'safe', vals, aux)
    for k in p_off:
        assert np.array_equal(p_off[k], p_safe[k]), \
            'safe mode param %r not bit-identical' % k
    for k in a_off:
        assert np.array_equal(a_off[k], a_safe[k]), \
            'safe mode aux %r not bit-identical' % k
    p_aggr, a_aggr = _train(net, 'aggressive', vals, aux)
    for k in p_off:
        np.testing.assert_allclose(p_off[k], p_aggr[k], rtol=1e-5,
                                   atol=1e-6, err_msg=k)
    for k in a_off:
        np.testing.assert_allclose(a_off[k], a_aggr[k], rtol=1e-5,
                                   atol=1e-6, err_msg=k)
    print('check_fusion: oracle parity holds '
          '(safe bit-for-bit, aggressive rtol 1e-5)')

    # -- 4: off is byte-identical to the pipeline-bypassed program ----------
    unpatched = fuse.apply_fuse_passes
    fuse.apply_fuse_passes = lambda s, t, mode=None: s   # pre-PR shape
    try:
        _, hlo_pre = _lower_step(net, 'off', vals, aux)
    finally:
        fuse.apply_fuse_passes = unpatched
    assert hlo_off == hlo_pre, \
        'MXTPU_FUSE=off program differs from the unfused program'
    print('check_fusion: off == unfused program (HLO byte-identical)')

    # -- 5: Prometheus exposition -------------------------------------------
    prom = instrument.render_prometheus()
    assert 'fuse_pass_' in prom.replace('.', '_') or \
        'fuse.pass.' in prom, 'no fuse.* series in exposition'
    assert 'fuse_cost' in prom.replace('.', '_') or \
        'fuse.cost' in prom, 'no fuse.cost series in exposition'
    print('check_fusion: OK')
    return 0


# ---------------------------------------------------------------------------
# hermetic parent
# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--child', action='store_true',
                    help=argparse.SUPPRESS)
    ap.add_argument('--min-bytes-drop', type=float, default=0.10)
    args = ap.parse_args(argv)

    if args.child:
        return _child(args.min_bytes_drop)

    env = dict(os.environ)
    env['JAX_PLATFORMS'] = 'cpu'
    for k in ('MXTPU_FUSE', 'MXTPU_FUSE_SKIP',
              'MXTPU_FORCE_PALLAS_INTERPRET', 'MXTPU_ASSUME_TPU'):
        env.pop(k, None)
    cmd = [sys.executable, os.path.abspath(__file__), '--child',
           '--min-bytes-drop', str(args.min_bytes_drop)]
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=600)
    sys.stderr.write(out.stderr)
    sys.stdout.write(out.stdout)
    return out.returncode


if __name__ == '__main__':
    sys.exit(main())
