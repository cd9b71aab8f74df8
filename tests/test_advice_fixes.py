"""Regression tests for the round-2 advisor findings (ADVICE.md):
storage view lifetime, atomic .so builds, Chrome-trace JSON escaping,
atexit dedup on engine-type toggles, WarpCTC shape diagnostics."""
import gc
import json
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import storage
from mxnet_tpu.engine import NativeEngine


def test_storage_view_keeps_buffer_alive():
    """A numpy view must keep the pooled block alive: dropping the
    PooledBuffer while the view is referenced cannot recycle the memory
    (use-after-free found in round 2)."""
    b = storage.alloc(4096)
    a = b.array((1024,), np.float32)
    live0 = storage.live_bytes()
    del b
    gc.collect()
    # still accounted live — the pool has NOT reclaimed the block
    assert storage.live_bytes() == live0
    a[:] = 3.0
    # a fresh allocation of the same bucket must not alias the view
    c = storage.alloc(4096)
    c.array((1024,), np.float32)[:] = 7.0
    assert (a == 3.0).all()
    c.direct_free()
    del a
    gc.collect()
    # dropping the last view finally releases the original block
    assert storage.live_bytes() == live0 - 4096


def test_storage_array_after_free_raises():
    b = storage.alloc(1024)
    b.free()
    with pytest.raises(RuntimeError):
        b.array((16,), np.float32)


def test_native_lib_follows_its_sources(tmp_path, monkeypatch):
    """The loaded library always corresponds to the src/*.cc in the
    tree: it is rebuilt when a source is newer than it (a .so left over
    from another commit is never loaded), and only then.  The build
    compiles to a temp name and renames into place, so a crashed or
    concurrent build can never leave a half-written .so at the load
    path."""
    from mxnet_tpu import _native
    src = tmp_path / 'a.cc'
    src.write_text('int x;')
    so = str(tmp_path / 'liba.so')
    builds = []

    def fake_compiler(cmd):
        out = cmd[cmd.index('-o') + 1]
        assert out != so and out.endswith('.tmp')
        with open(out, 'w') as f:
            f.write('so %d' % len(builds))
        builds.append(out)

    monkeypatch.setattr(_native.subprocess, 'check_call', fake_compiler)
    assert _native._fresh_so(so, [str(src)], []) == so
    assert len(builds) == 1 and os.path.exists(so)
    _native._fresh_so(so, [str(src)], [])
    assert len(builds) == 1                     # fresh: left alone
    newer = os.path.getmtime(so) + 10
    os.utime(str(src), (newer, newer))
    _native._fresh_so(so, [str(src)], [])
    assert len(builds) == 2                     # stale: rebuilt
    assert not [f for f in os.listdir(str(tmp_path)) if f.endswith('.tmp')]


def test_chrome_trace_escapes_op_names(tmp_path):
    """Op hints with quotes/backslashes/newlines must still produce valid
    Chrome-trace JSON (src/engine.cc JsonEscape)."""
    eng = NativeEngine(num_workers=1)
    eng.set_profiling(True)
    v = eng.new_var()
    evil = 'op "quoted" back\\slash\nnewline\ttab'
    eng.push(lambda: time.sleep(0.001), mutable_vars=[v], name=evil)
    eng.wait_for_all()
    path = tmp_path / 'trace.json'
    eng.dump_profile(str(path))
    trace = json.loads(path.read_text())   # must parse
    names = [e['name'] for e in trace['traceEvents']]
    assert evil in names
    eng.dispose()


def test_atexit_registered_once():
    """Engine-type toggles rebuild the engine but must not stack another
    atexit hook per rebuild."""
    from mxnet_tpu import engine as eng_mod
    eng_mod.native_engine()
    assert eng_mod._atexit_registered
    calls = []
    import atexit
    orig = atexit.register
    atexit.register = lambda *a, **k: calls.append(a) or orig(*a, **k)
    try:
        eng_mod.set_engine_type('NaiveEngine')
        eng_mod.native_engine()
        eng_mod.set_engine_type('ThreadedEnginePerDevice')
        eng_mod.native_engine()
    finally:
        atexit.register = orig
    assert not [c for c in calls
                if c and c[0] is eng_mod._shutdown_native_engine]


def test_warpctc_shape_errors_are_informative():
    import jax.numpy as jnp
    from mxnet_tpu.ops.registry import get_op
    op = get_op('WarpCTC')
    data = jnp.zeros((7, 5))        # 7 rows not divisible by input_length=3
    label = jnp.zeros((4,))
    with pytest.raises(ValueError, match='input_length'):
        op.apply({'label_length': 2, 'input_length': 3},
                 [data, label], True, None)
    data = jnp.zeros((6, 5))
    label = jnp.zeros((5,))         # batch=2 * label_length=2 != 5
    with pytest.raises(ValueError, match='label'):
        op.apply({'label_length': 2, 'input_length': 3},
                 [data, label], True, None)


# ---------------------------------------------------------------------------
# round-4 advisor findings
# ---------------------------------------------------------------------------

def test_attention_cpu_short_seq_uses_reference():
    """Advice r4: the interpreted Pallas kernel is orders of magnitude
    slower than XLA on short/medium sequences — the CPU default must
    route those to the reference path and only long sequences to the
    interpreter."""
    from mxnet_tpu.ops import pallas_attention as pa
    assert pa._mode(seq_len=128) == 'reference'
    assert pa._mode(seq_len=pa.INTERPRET_MIN_SEQ - 8) == 'reference'
    assert pa._mode(seq_len=pa.INTERPRET_MIN_SEQ) == 'interpret'
    # the explicit force knob still wins at any length
    os.environ['MXTPU_FORCE_PALLAS_INTERPRET'] = '1'
    try:
        assert pa._mode(seq_len=128) == 'interpret'
    finally:
        del os.environ['MXTPU_FORCE_PALLAS_INTERPRET']


def test_max_pool_large_window_routes_to_reduce_window():
    """Advice r4: >25-tap windows go through reduce_window (since PR 27
    every window does; the unrolled firstmax form they had to avoid is
    gone) — and the result is still correct."""
    import jax
    x = mx.sym.Variable('x')
    y = mx.sym.Pooling(x, kernel=(11, 11), stride=(4, 4),
                       pool_type='max', name='p')
    ex = y.simple_bind(ctx=mx.cpu(), x=(1, 2, 32, 32))
    data = np.random.RandomState(0).rand(1, 2, 32, 32).astype(np.float32)
    ex.forward(is_train=False, x=data)
    got = ex.outputs[0].asnumpy()
    # brute-force window max
    want = np.full_like(got, -np.inf)
    for oy in range(got.shape[2]):
        for ox in range(got.shape[3]):
            want[:, :, oy, ox] = data[:, :, oy * 4:oy * 4 + 11,
                                      ox * 4:ox * 4 + 11].max((2, 3))
    assert np.allclose(got, want), np.abs(got - want).max()


def test_zero_momentum_matches_plain_sgd_state():
    """Advice r4: the ZeRO momentum buffer uses the same lr-folded
    formulation as make_sgd_momentum, so optimizer state (not just the
    trajectory) is interchangeable with the non-ZeRO path."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel.zero import (make_zero_sgd_momentum,
                                         zero_opt_init, _layout)
    from mxnet_tpu.parallel.train_step import (make_sgd_momentum,
                                               sgd_momentum_init)
    n = 4
    devs = jax.devices()[:n]
    mesh = Mesh(np.array(devs), ('dp',))
    rng = np.random.RandomState(1)
    params = {'w': jnp.asarray(rng.randn(6, 5).astype(np.float32)),
              'b': jnp.asarray(rng.randn(5).astype(np.float32))}
    grads = {k: jnp.asarray(rng.randn(*v.shape).astype(np.float32))
             for k, v in params.items()}
    lr, mu, wd = 0.1, 0.9, 1e-3
    update = make_zero_sgd_momentum('dp', n, lr=lr, momentum=mu, wd=wd,
                                    rescale_grad=1.0 / n)
    mom0 = zero_opt_init(params, n)

    def step(p, g, m):
        return update(p, g, m)

    sharded = shard_map(step, mesh=mesh,
                        in_specs=(P(), P(), P('dp')),
                        out_specs=(P(), P('dp')), check_vma=False)
    # feed the same grad on every device: psum_scatter sums n copies,
    # rescale 1/n recovers the single-device gradient
    new_p, new_m = sharded(params, grads, mom0)

    ref_update = make_sgd_momentum(lr=lr, momentum=mu, wd=wd,
                                   rescale_grad=1.0)
    ref_p, ref_m = ref_update(params, grads, sgd_momentum_init(params))
    for k in params:
        assert np.allclose(np.asarray(new_p[k]), np.asarray(ref_p[k]),
                           atol=1e-5), k
    # state interchangeability: the fused ZeRO buffer holds exactly the
    # per-param lr-folded momenta
    names, chunks, offsets, _ = _layout(params, n)
    flat = np.asarray(new_m).reshape(-1)
    for k in params:
        size = int(np.prod(params[k].shape))
        # rows are per-device shards of the fused (C,) vector
        fused = np.asarray(new_m).reshape(n, -1)
        vec = np.concatenate([fused[i] for i in range(n)])
        # reconstruct this param's slice across shards
        got = np.concatenate(
            [fused[i, offsets[k]:offsets[k] + chunks[k]]
             for i in range(n)])[:size].reshape(params[k].shape)
        assert np.allclose(got, np.asarray(ref_m[k]), atol=1e-5), k


def test_nhwc_transpose_names_include_output_index():
    """Advice r4: transposes inserted for different outputs of a
    multi-output node must carry distinct names — checked against the
    actual naming authority `_nhwc_regions` uses."""
    from mxnet_tpu.fuse import _layout_transpose_name
    names = {_layout_transpose_name('split0', idx, 'NHWC')
             for idx in (0, 1, 2)}
    assert len(names) == 3, names
    assert _layout_transpose_name('split0', 0, 'NHWC') == \
        'split0_to_nhwc'
    assert _layout_transpose_name('split0', 2, 'NCHW') == \
        'split0_out2_to_nchw'
