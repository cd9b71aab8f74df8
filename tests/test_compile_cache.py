"""Tier-1 tests for the warm-start compile subsystem (ISSUE 4):
persistent compilation cache + AOT warmup manifest + bucket/shape
precompile (mxnet_tpu/compile_cache.py), plus the satellite fixes that
ride along (optimizer multi_precision master-state policy, imperative
jit-cache hit/miss counters).

The acceptance scenario — a warm-start ``Module.fit`` records
``compile.cache_hits > 0`` and strictly fewer ``executor.xla_traces``
than the cold run against the same ``MXTPU_COMPILE_CACHE`` — runs as
the two-process ``tools/check_compile.py`` smoke (the parent process
imports neither jax nor mxnet, so the cost is two child startups).
"""
import json
import os
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compile_cache, instrument
from mxnet_tpu import optimizer as opt_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECK_COMPILE = os.path.join(REPO, 'tools', 'check_compile.py')


@pytest.fixture(autouse=True)
def _clean_instrument_state():
    prof, met = instrument.profiling_enabled(), instrument.metrics_enabled()
    instrument.clear_trace()
    instrument.reset_metrics()
    yield
    instrument.set_profiling(prof)
    instrument.set_metrics(met)
    instrument.clear_trace()
    instrument.reset_metrics()


def _mlp(d_in=8, classes=4):
    net = mx.sym.Variable('data')
    net = mx.sym.FullyConnected(net, num_hidden=16, name='fc1')
    net = mx.sym.Activation(net, act_type='relu', name='act1')
    net = mx.sym.FullyConnected(net, num_hidden=classes, name='fc2')
    return mx.sym.SoftmaxOutput(net, name='softmax')


def _cls_data(rng, n, d, classes):
    X = rng.randn(n, d).astype(np.float32)
    Y = (X @ rng.randn(d, classes)).argmax(1).astype(np.float32)
    return X, Y


# ---------------------------------------------------------------------------
# Acceptance: two-process cold/warm against one persistent cache
# ---------------------------------------------------------------------------

def test_check_compile_two_process_smoke():
    """Cold run writes cache + manifest; warm run reuses executables
    from disk (compile.cache_hits > 0), takes STRICTLY fewer hot-path
    traces, and trains to identical parameters."""
    assert subprocess.call([sys.executable, CHECK_COMPILE]) == 0


# ---------------------------------------------------------------------------
# In-process warm start (no cache dir needed: AOT pre-compile alone)
# ---------------------------------------------------------------------------

def test_warm_start_in_process_parity_and_zero_hot_traces():
    """fit(warm_start=True) must (a) run the whole epoch from AOT
    executables — zero executor.xla_traces, warmup accounted separately
    — and (b) be bit-for-bit the cold run: warm start may move compiles
    around, never change numerics."""
    instrument.set_metrics(True)
    rng = np.random.RandomState(0)
    X, Y = _cls_data(rng, 64, 8, 4)

    def run(warm):
        instrument.reset_metrics()
        mx.random.seed(5)
        it = mx.io.NDArrayIter(X, Y, batch_size=16)
        mod = mx.mod.Module(_mlp(), context=mx.cpu())
        mod.fit(it, num_epoch=2, optimizer='sgd',
                optimizer_params={'learning_rate': 0.1, 'momentum': 0.9},
                eval_metric='acc', initializer=mx.init.Uniform(0.05),
                warm_start=warm)
        params, _ = mod.get_params()
        return ({k: v.asnumpy() for k, v in params.items()},
                instrument.metrics_snapshot()['counters'])

    cold_params, cold_c = run(False)
    assert cold_c.get('executor.xla_traces', 0) >= 1
    warm_params, warm_c = run(True)
    assert warm_c.get('executor.xla_traces', 0) == 0, warm_c
    assert warm_c.get('compile.warmup_traces', 0) >= 1
    assert warm_c.get('compile.aot_calls', 0) == 8      # 4 batches x 2
    assert warm_c.get('compile.warmup_errors', 0) == 0
    for k in cold_params:
        assert np.array_equal(cold_params[k], warm_params[k]), k


# ---------------------------------------------------------------------------
# Bucketing: one trace per distinct bucket (lazy) / zero (precompiled)
# ---------------------------------------------------------------------------

def _bucket_sym_gen(classes=4):
    """Variable-length input (bs, key) reduced over the length axis, so
    parameter shapes are key-independent and buckets share storage —
    the weight-sharing contract real seq-length bucketing relies on."""
    def sym_gen(key):
        net = mx.sym.Variable('data')
        net = mx.sym.mean(net, axis=1, keepdims=True, name='pool')
        net = mx.sym.FullyConnected(net, num_hidden=8, name='fc1')
        net = mx.sym.FullyConnected(net, num_hidden=classes, name='fc2')
        net = mx.sym.SoftmaxOutput(net, name='softmax')
        return net, ('data',), ('softmax_label',)
    return sym_gen


class _BucketIter(mx.io.DataIter):
    """Two buckets (input widths 8 and 16), interleaved."""

    def __init__(self, bs=4, keys=(8, 16, 8, 16), classes=4):
        super().__init__()
        self.batch_size = bs
        self._keys = list(keys)
        self._classes = classes
        self._i = 0
        self._rng = np.random.RandomState(3)

    @property
    def provide_data(self):
        return [('data', (self.batch_size, self._keys[0]))]

    @property
    def provide_label(self):
        return [('softmax_label', (self.batch_size,))]

    def reset(self):
        self._i = 0

    def next(self):
        if self._i >= len(self._keys):
            raise StopIteration
        key = self._keys[self._i]
        self._i += 1
        data = mx.nd.array(
            self._rng.randn(self.batch_size, key).astype(np.float32))
        label = mx.nd.array(self._rng.randint(
            0, self._classes, (self.batch_size,)).astype(np.float32))
        return mx.io.DataBatch(
            [data], [label], pad=0, bucket_key=key,
            provide_data=[('data', (self.batch_size, key))],
            provide_label=[('softmax_label', (self.batch_size,))])


def test_bucketing_one_trace_per_distinct_bucket():
    """The lazy path: exactly one executor.xla_traces increment per
    DISTINCT bucket, zero on repeats — the guard for both the lazy
    bucket binding and the precompile path's accounting."""
    instrument.set_metrics(True)
    instrument.reset_metrics()
    mod = mx.module.BucketingModule(_bucket_sym_gen(),
                                    default_bucket_key=8,
                                    context=mx.cpu())
    mod.fit(_BucketIter(), num_epoch=2, optimizer='sgd',
            optimizer_params={'learning_rate': 0.1},
            eval_metric='acc', initializer=mx.init.Uniform(0.05))
    snap = instrument.metrics_snapshot()['counters']
    assert len(mod._buckets) == 2
    # 2 distinct buckets, 4 batches/epoch, 2 epochs: a repeated bucket
    # (same epoch or the next) must never re-trace
    assert snap.get('executor.xla_traces', 0) == 2, snap


def test_bucketing_precompile_declared_buckets():
    """MXTPU_PRECOMPILE_BUCKETS + bucket_keys: every declared bucket is
    bound and AOT-compiled at fit start — zero hot-path traces even for
    a bucket first seen mid-epoch; warmup traces accounted to
    compile.warmup_traces."""
    instrument.set_metrics(True)
    instrument.reset_metrics()
    saved = os.environ.get('MXTPU_PRECOMPILE_BUCKETS')
    os.environ['MXTPU_PRECOMPILE_BUCKETS'] = '1'
    try:
        # one bare key (shape-substitution heuristic) and one explicit
        # (key, data_shapes, label_shapes) declaration — both forms
        # must precompile
        mod = mx.module.BucketingModule(
            _bucket_sym_gen(), default_bucket_key=8, context=mx.cpu(),
            bucket_keys=[8, (16, [('data', (4, 16))],
                              [('softmax_label', (4,))])])
        mod.fit(_BucketIter(), num_epoch=2, optimizer='sgd',
                optimizer_params={'learning_rate': 0.1},
                eval_metric='acc', initializer=mx.init.Uniform(0.05))
        snap = instrument.metrics_snapshot()['counters']
        assert len(mod._buckets) == 2
        assert snap.get('executor.xla_traces', 0) == 0, snap
        assert snap.get('compile.warmup_traces', 0) >= 2, snap
        assert snap.get('compile.aot_calls', 0) == 8, snap
        assert snap.get('compile.warmup_errors', 0) == 0, snap
    finally:
        if saved is None:
            os.environ.pop('MXTPU_PRECOMPILE_BUCKETS', None)
        else:
            os.environ['MXTPU_PRECOMPILE_BUCKETS'] = saved


# ---------------------------------------------------------------------------
# pow2 shape policy
# ---------------------------------------------------------------------------

def test_pad_to_bucket_values():
    assert [compile_cache.pad_to_bucket(n) for n in
            (1, 2, 3, 4, 5, 7, 8, 9, 100)] == \
        [1, 2, 4, 4, 8, 8, 8, 16, 128]
    assert compile_cache.pad_to_bucket(3, minimum=16) == 16


def test_predictor_pad_to_bucket():
    """Varying request batch sizes land on O(log) pow2 buckets: results
    match the exact-shape predictor, outputs are sliced to the real row
    count, and compile.shape_buckets counts the distinct buckets."""
    instrument.set_metrics(True)
    instrument.reset_metrics()
    rng = np.random.RandomState(2)
    W = rng.randn(3, 8).astype(np.float32)
    b = rng.randn(3).astype(np.float32)
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable('data'), num_hidden=3,
                              name='fc'), name='softmax')
    params = {'fc_weight': mx.nd.array(W), 'fc_bias': mx.nd.array(b)}
    exact = mx.predictor.Predictor(net, dict(params), {'data': (16, 8)})
    padded = mx.predictor.Predictor(net, dict(params), {'data': (16, 8)},
                                    pad_to_bucket=True)
    X = rng.randn(16, 8).astype(np.float32)
    exact.forward(data=X)
    ref = exact.get_output(0)
    for rows in (3, 5, 9, 6):
        padded.forward(data=X[:rows])
        out = padded.get_output(0)
        assert out.shape == (rows, 3)
        np.testing.assert_allclose(out, ref[:rows], rtol=1e-5, atol=1e-6)
    # rows 3 -> bucket 4; 5, 6 -> 8; 9 -> 16: three distinct programs
    assert sorted(padded._bucket_execs) == [4, 8, 16]
    snap = instrument.metrics_snapshot()
    assert snap['counters'].get('compile.shape_buckets') == 3


# ---------------------------------------------------------------------------
# Manifest unit behavior
# ---------------------------------------------------------------------------

def test_manifest_record_dedup_and_reload(tmp_path):
    path = str(tmp_path / 'manifest.json')
    entry = {'kind': 'fit_step', 'fp': 'abc123',
             'meta': {'metric': None, 'compute_dtype': None},
             'batch': {'data': [[16, 8], 'float32']}}
    m = compile_cache._Manifest(path)
    assert m.record(dict(entry))
    assert not m.record(dict(entry))          # dedup
    assert m.record({**entry, 'fp': 'other'})
    # a fresh instance (a new process) reloads both entries
    m2 = compile_cache._Manifest(path)
    assert len(m2.entries()) == 2
    assert len(m2.entries(kind='fit_step', fp='abc123')) == 1
    ent = m2.entries(fp='abc123')[0]
    assert ent['batch'] == {'data': [[16, 8], 'float32']}
    # the file itself is valid JSON (atomic_replace committed it whole)
    with open(path) as f:
        assert len(json.load(f)['traces']) == 2


def test_manifest_cap(tmp_path):
    m = compile_cache._Manifest(str(tmp_path / 'manifest.json'))
    for i in range(compile_cache.MANIFEST_CAP + 10):
        m.record({'kind': 'fit_step', 'fp': 'f%d' % i})
    assert len(m.entries()) == compile_cache.MANIFEST_CAP


def test_jsonable_normalizes_fold_keys():
    key = ('mxnet_tpu.metric', 'Accuracy', (1, 2.5, None))
    assert compile_cache.jsonable(key) == \
        ['mxnet_tpu.metric', 'Accuracy', [1, 2.5, None]]
    # round trip through JSON is a fixed point — manifest comparisons
    # run on this form
    assert json.loads(json.dumps(compile_cache.jsonable(key))) == \
        compile_cache.jsonable(key)


# ---------------------------------------------------------------------------
# Satellite: optimizer multi_precision master-state policy
# ---------------------------------------------------------------------------

def test_multi_precision_state_dtype():
    """create_state follows the WEIGHT dtype by default (the seed
    hardcoded float32 for AdaGrad/RMSProp) and keeps float32 master
    state under multi_precision=True."""
    w16 = mx.nd.zeros((4,), dtype=jnp.bfloat16)
    w32 = mx.nd.zeros((4,), dtype=np.float32)

    ada = opt_mod.AdaGrad()
    assert np.dtype(ada.create_state(0, w16).dtype) == jnp.bfloat16
    assert np.dtype(ada.create_state(0, w32).dtype) == np.float32
    ada_mp = opt_mod.AdaGrad(multi_precision=True)
    assert np.dtype(ada_mp.create_state(0, w16).dtype) == np.float32

    sgd = opt_mod.SGD(momentum=0.9)
    assert np.dtype(sgd.create_state(0, w16).dtype) == jnp.bfloat16
    sgd_mp = opt_mod.SGD(momentum=0.9, multi_precision=True)
    assert np.dtype(sgd_mp.create_state(0, w16).dtype) == np.float32

    rms = opt_mod.RMSProp(centered=True, multi_precision=True)
    assert all(np.dtype(s.dtype) == np.float32
               for s in rms.create_state(0, w16))


def test_multi_precision_functional_init_and_update():
    """The functional (fused-path) form honors the same policy, and the
    updated weight keeps ITS dtype under a float32 master state."""
    w = jnp.zeros((4,), jnp.bfloat16)
    g = jnp.ones((4,), jnp.bfloat16)

    for make in (lambda mp: opt_mod.AdaGrad(multi_precision=mp),
                 lambda mp: opt_mod.SGD(momentum=0.9, multi_precision=mp),
                 lambda mp: opt_mod.Adam(multi_precision=mp)):
        fo = make(False).make_functional(['w'])
        st = fo.init({'w': w})['w']
        leaves = st if isinstance(st, tuple) else (st,)
        assert all(leaf.dtype == jnp.bfloat16 for leaf in leaves), make

        fo_mp = make(True).make_functional(['w'])
        st_mp = fo_mp.init({'w': w})
        leaves = st_mp['w'] if isinstance(st_mp['w'], tuple) \
            else (st_mp['w'],)
        assert all(leaf.dtype == np.float32 for leaf in leaves), make
        new_p, new_s = fo_mp.update({'w': w}, {'w': g}, st_mp,
                                    jnp.float32(0.1))
        assert new_p['w'].dtype == jnp.bfloat16
        leaves = new_s['w'] if isinstance(new_s['w'], tuple) \
            else (new_s['w'],)
        assert all(leaf.dtype == np.float32 for leaf in leaves)


def test_multi_precision_interacts_with_compute_dtype():
    """The fused bf16 fit keeps float32 MASTER params, so optimizer
    state stays float32 with or without the flag — the structural
    master-weight discipline the flag makes explicit for the
    imperative path."""
    rng = np.random.RandomState(1)
    X, Y = _cls_data(rng, 32, 8, 4)
    it = mx.io.NDArrayIter(X, Y, batch_size=16)
    mod = mx.mod.Module(_mlp(), context=mx.cpu(),
                        compute_dtype=jnp.bfloat16)
    mod.fit(it, num_epoch=1, optimizer='sgd',
            optimizer_params={'learning_rate': 0.1, 'momentum': 0.9},
            eval_metric='acc', initializer=mx.init.Uniform(0.05))
    assert mod._fused is not None
    assert all(s.dtype == np.float32
               for s in mod._fused_opt_state.values())


# ---------------------------------------------------------------------------
# Satellite: imperative jit-cache visibility in compile.*
# ---------------------------------------------------------------------------

def test_imperative_cache_counters():
    instrument.set_metrics(True)
    instrument.reset_metrics()
    a = mx.nd.array(np.arange(4.0, dtype=np.float32))
    # unique clip bounds => a fresh cache key: first call misses, the
    # repeat hits
    mx.nd.clip(a, -977.25, 977.25)
    before = instrument.metrics_snapshot()['counters']
    assert before.get('compile.imperative_cache_misses', 0) >= 1
    mx.nd.clip(a, -977.25, 977.25)
    after = instrument.metrics_snapshot()['counters']
    assert after.get('compile.imperative_cache_hits', 0) >= \
        before.get('compile.imperative_cache_hits', 0) + 1


# ---------------------------------------------------------------------------
# Knobs off: nothing installed, off path allocation-free
# ---------------------------------------------------------------------------

def test_cache_dir_rule(monkeypatch):
    """One function decides where compiled programs persist:
    JAX_COMPILATION_CACHE_DIR, else MXTPU_COMPILE_CACHE, else (run
    scripts only) the fixed <checkout>/.jax_cache — never a directory
    made from tempfile, a pid or a time, which could not hit twice."""
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
    monkeypatch.delenv('MXTPU_COMPILE_CACHE', raising=False)
    assert compile_cache.resolve_cache_dir() == (None, False)
    assert compile_cache.resolve_cache_dir(checkout_default=True) == \
        (os.path.join(REPO, '.jax_cache'), False)
    monkeypatch.setenv('MXTPU_COMPILE_CACHE', '/m')
    assert compile_cache.resolve_cache_dir(checkout_default=True) == \
        ('/m', False)
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', '/x')
    assert compile_cache.resolve_cache_dir(checkout_default=True) == \
        ('/x', True)


def test_env_cache_dir_wins_and_jax_config_is_left_alone(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set JAX keeps its own setting: no
    code calls jax.config.update('jax_compilation_cache_dir', ...), the
    cache and the manifest both land in that directory, and a run
    script's checkout default is not consulted."""
    code = (
        "import os, jax\n"
        "calls, real = [], jax.config.update\n"
        "jax.config.update = lambda k, v: (calls.append(k), "
        "real(k, v))[1]\n"
        "import jax.numpy as jnp\n"
        "from mxnet_tpu import compile_cache\n"
        "d = compile_cache.ensure_persistent_cache("
        "checkout_default=True)\n"
        "assert d == os.environ['JAX_COMPILATION_CACHE_DIR'], d\n"
        "assert compile_cache.manifest_path().startswith(d)\n"
        "assert 'jax_compilation_cache_dir' not in calls, calls\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()\n"
        "print('CACHE-RULE-OK')\n")
    cache = tmp_path / 'x'
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache),
               MXTPU_COMPILE_CACHE=str(tmp_path / 'loses'),
               JAX_PLATFORMS='cpu')
    proc = subprocess.run([sys.executable, '-c', code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert 'CACHE-RULE-OK' in proc.stdout, proc.stderr[-1500:]
    assert os.listdir(str(cache)), 'nothing was cached in the env dir'
    assert not (tmp_path / 'loses').exists()


def test_knobs_off_nothing_installed():
    assert not os.environ.get('JAX_COMPILATION_CACHE_DIR')
    assert not os.environ.get('MXTPU_COMPILE_CACHE')
    assert compile_cache.ensure_persistent_cache() is None
    assert compile_cache.cache_dir() is None
    assert compile_cache.manifest_path() is None
    assert compile_cache.manifest_entries() == []


def test_count_trace_off_path_overhead_guard():
    """With metrics off, count_trace must stay a bare flag check (the
    same guard discipline as tests/test_instrument.py): the traced()
    wrapper only ever runs at jit-trace time, but count_trace is its
    unconditionally-executed first line, so IT is the off path."""
    _flag = False

    def floor(name):
        if not _flag:
            return

    n = 10000

    def timeit(fn):
        best = float('inf')
        for _ in range(7):
            t0 = time.perf_counter()
            for _i in range(n):
                fn('bench')
            best = min(best, time.perf_counter() - t0)
        return best

    assert not instrument.metrics_enabled()
    ratio = min(timeit(instrument.count_trace) / timeit(floor)
                for _ in range(3))
    assert ratio < 2.0, 'off-path count_trace is %.2fx the floor' % ratio
