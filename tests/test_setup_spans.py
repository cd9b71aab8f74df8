"""The time before the first step and the host's stalls on the
performance plane's seam: ``perf.setup.*`` spans round ``BaseModule.fit``'s
set-up, the compile listener's ``compile.*_secs`` histograms and
``compile.programs``, ``perf.phase.compile`` round the program's own
``lower().compile()``, and ``perf.gc`` / ``perf.gc_full`` for full
collections of Python's collector."""
import gc
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compile_cache, instrument, perfwatch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, 'tools'))
import check_trace  # noqa: E402

SETUP = ('bind', 'init_params', 'init_optimizer', 'warm_start')
TRACE = '/jax/core/compile/jaxpr_trace_duration'
LOWER = '/jax/core/compile/jaxpr_to_mlir_module_duration'
BACKEND = '/jax/core/compile/backend_compile_duration'
CACHE_READ = '/jax/compilation_cache/cache_retrieval_time_sec'


@pytest.fixture(autouse=True)
def _clean_state():
    prof, met = instrument.profiling_enabled(), instrument.metrics_enabled()
    instrument.clear_trace()
    instrument.reset_metrics()
    perfwatch.set_enabled(False)
    yield
    perfwatch.set_enabled(False)
    instrument.set_profiling(prof)
    instrument.set_metrics(met)
    instrument.clear_trace()
    instrument.reset_metrics()


def _fit(warm_start=False, batches=4, plane=True):
    rng = np.random.RandomState(23)
    X = rng.randn(8 * batches, 10).astype(np.float32)
    Y = rng.randint(0, 4, 8 * batches).astype(np.float32)
    net = mx.sym.FullyConnected(mx.sym.Variable('data'), num_hidden=4,
                                name='sfc')
    net = mx.sym.SoftmaxOutput(net, name='softmax')
    saved = os.environ.get('MXTPU_PERFWATCH')
    os.environ['MXTPU_PERFWATCH'] = '1' if plane else ''
    try:
        mx.random.seed(3)
        mx.mod.Module(net).fit(
            mx.io.NDArrayIter(X, Y, batch_size=8), num_epoch=1,
            optimizer='sgd', optimizer_params={'learning_rate': 0.1},
            initializer=mx.init.Uniform(0.05), warm_start=warm_start)
    finally:
        if saved is None:
            os.environ.pop('MXTPU_PERFWATCH', None)
        else:
            os.environ['MXTPU_PERFWATCH'] = saved
    return instrument.metrics_snapshot()


def test_a_fit_under_the_plane_fills_the_setup_and_compile_series():
    perfwatch.set_enabled(True)
    snap = _fit(warm_start=True)
    hists = snap['histograms']
    for name in SETUP:
        assert hists['perf.setup.' + name]['count'] == 1, name
    for name in ('trace', 'lower', 'backend'):
        assert hists['compile.%s_secs' % name]['count'] >= 1, name
        assert hists['compile.%s_secs' % name]['sum'] > 0, name
    # one count a backend compile, compiled or fetched
    assert snap['counters']['compile.programs'] == \
        hists['compile.backend_secs']['count']
    # the warm start's build on the warmup pool
    assert hists['perf.phase.compile']['count'] >= 1
    # the parameters' initialisation compiles inside its span
    assert hists['perf.setup.init_params']['sum'] > 0
    assert 'compile.time_saved_secs' not in snap.get('timers', {})


def test_with_the_plane_off_setup_is_the_shared_no_op():
    assert perfwatch.setup('bind') is instrument.NULL_CTX
    assert perfwatch._on_collect not in gc.callbacks
    perfwatch.set_enabled(True)
    assert perfwatch.setup('bind') is not instrument.NULL_CTX
    assert gc.callbacks.count(perfwatch._on_collect) == 1
    perfwatch.set_enabled(True)                 # registered once
    assert gc.callbacks.count(perfwatch._on_collect) == 1
    perfwatch.set_enabled(False)
    assert perfwatch._on_collect not in gc.callbacks
    # a fit with the registry on and the plane off writes no set-up or
    # collector series
    instrument.reset_metrics()
    instrument.set_metrics(True)
    snap = _fit(warm_start=True, plane=False)
    assert perfwatch._on_collect not in gc.callbacks
    assert not [k for k in snap.get('histograms', {})
                if k.startswith(('perf.setup.', 'perf.gc', 'perf.phase.'))]
    assert 'perf.gc_full' not in snap['counters']
    # the registry alone still times the compiles
    assert snap['histograms']['compile.backend_secs']['count'] >= 1


@pytest.mark.parametrize('plane', ['off', 'on'])
def test_import_registers_a_collector_callback_only_with_the_plane(plane):
    """Off, ``gc.callbacks`` after ``import mxnet_tpu`` is what it was
    before; on, the callback is there from import, before any fit."""
    code = ("import gc, jax, numpy\n"
            "before = list(gc.callbacks)\n"
            "import mxnet_tpu\n"
            "from mxnet_tpu import perfwatch\n"
            "added = [c for c in gc.callbacks if c not in before]\n"
            "print('ADDED', [c.__name__ for c in added])\n"
            "print('SAME', gc.callbacks[:len(before)] == before)\n")
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.pop('MXTPU_PERFWATCH', None)
    if plane == 'on':
        env['MXTPU_PERFWATCH'] = '1'
    done = subprocess.run([sys.executable, '-c', code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    assert 'SAME True' in done.stdout
    want = ['_on_collect'] if plane == 'on' else []
    assert 'ADDED %r' % want in done.stdout


def test_a_full_collection_is_one_span_and_one_count():
    perfwatch.set_enabled(True)
    instrument.set_profiling(True)
    was_enabled = gc.isenabled()
    gc.disable()                # no collection of the allocator's own
    try:
        gc.collect()
        snap = instrument.metrics_snapshot()
        assert snap['histograms']['perf.gc']['count'] == 1
        assert snap['counters']['perf.gc_full'] == 1
        gc.collect(0)
        gc.collect(1)
        snap = instrument.metrics_snapshot()
        assert snap['histograms']['perf.gc']['count'] == 1
        assert snap['counters']['perf.gc_full'] == 1
        spans = [e for e in instrument.trace_events()
                 if e.get('name') == 'perf.gc']
        assert len(spans) == 1 and spans[0]['cat'] == 'gc'
        perfwatch.set_enabled(False)
        gc.collect()
        snap = instrument.metrics_snapshot()
        assert snap['counters']['perf.gc_full'] == 1
    finally:
        if was_enabled:
            gc.enable()


def test_a_plane_turned_on_reads_zero_collections_until_one_runs():
    perfwatch.set_enabled(True)
    snap = instrument.metrics_snapshot()
    assert snap['histograms']['perf.gc']['count'] == 0
    assert snap['counters']['perf.gc_full'] == 0


def _emit(*events):
    """Replay JAX's compile events: ``('start', name)`` is the scalar JAX
    records on entering a timed event, ``(name, seconds)`` its end."""
    from jax._src import monitoring
    for event in events:
        if event[0] == 'start':
            monitoring.record_scalar(event[1], 0.0, fun_name='f')
        else:
            monitoring.record_event_duration_secs(event[0], event[1],
                                                  fun_name='f')


def test_a_compile_event_inside_another_counts_once_in_the_innermost():
    instrument.set_metrics(True)
    compile_cache._install_listeners()          # idempotent
    # an outer trace of 0.5 s runs a jit whose trace, lowering and
    # backend compile take 0.1, 0.05 and 0.2 s; then the outer function
    # lowers in 0.3 s and compiles in 0.7 s, 0.25 s of it a cache read
    _emit(('start', TRACE),
          ('start', TRACE), (TRACE, 0.1),
          ('start', LOWER), (LOWER, 0.05),
          ('start', BACKEND), (BACKEND, 0.2),
          (TRACE, 0.5),
          ('start', LOWER), (LOWER, 0.3),
          ('start', BACKEND), (CACHE_READ, 0.25), (BACKEND, 0.7))
    hists = instrument.metrics_snapshot()['histograms']
    assert hists['compile.trace_secs']['count'] == 2
    assert hists['compile.trace_secs']['sum'] == pytest.approx(
        0.1 + (0.5 - 0.1 - 0.05 - 0.2))
    assert hists['compile.lower_secs']['sum'] == pytest.approx(0.35)
    assert hists['compile.backend_secs']['sum'] == pytest.approx(0.9)
    assert hists['compile.cache_read_secs']['sum'] == pytest.approx(0.25)
    assert instrument.metrics_snapshot()['counters'][
        'compile.programs'] == 2
    # the three add up to the wall time spent in them
    total = sum(hists['compile.%s_secs' % k]['sum']
                for k in ('trace', 'lower', 'backend'))
    assert total == pytest.approx(0.5 + 0.3 + 0.7)


def test_with_the_registry_off_the_listener_records_nothing():
    compile_cache._install_listeners()
    instrument.set_metrics(False)
    _emit(('start', TRACE), (TRACE, 0.1), ('start', BACKEND),
          (BACKEND, 0.2), (CACHE_READ, 0.1))
    instrument.set_metrics(True)
    snap = instrument.metrics_snapshot()
    assert not [k for k in snap.get('histograms', {})
                if k.startswith('compile.')]
    assert 'compile.programs' not in snap['counters']


def test_the_registry_turning_on_installs_the_listener_once():
    calls = []
    instrument.set_metrics(False)
    instrument.when_metrics_on(lambda: calls.append(1))
    assert calls == []
    instrument.set_metrics(True)
    instrument.set_metrics(True)
    assert calls == [1]
    instrument.when_metrics_on(lambda: calls.append(2))
    assert calls == [1, 2]
    from jax._src import monitoring
    compile_cache._install_listeners()
    compile_cache._install_listeners()
    names = [getattr(f, '__qualname__', '') for f in
             monitoring.get_event_duration_listeners()]
    assert sum('_install_listeners' in n for n in names) == 1


def test_a_second_process_on_the_same_cache_reads_it(tmp_path):
    """The second process fetches what the first compiled: its backend
    compiles hold cache reads, ``compile.cache_read_secs``."""
    code = ("import json, jax, jax.numpy as jnp\n"
            "from mxnet_tpu import compile_cache, instrument\n"
            "compile_cache.ensure_persistent_cache()\n"
            "jax.jit(lambda x: jnp.tanh(x) * 3 + 1)(jnp.ones(16))"
            ".block_until_ready()\n"
            "s = instrument.metrics_snapshot()\n"
            "print('SNAP ' + json.dumps({'h': s.get('histograms', {}), "
            "'c': s['counters']}))\n")
    env = dict(os.environ, JAX_PLATFORMS='cpu', MXTPU_METRICS='1',
               MXTPU_COMPILE_CACHE=str(tmp_path / 'cache'))
    env.pop('JAX_COMPILATION_CACHE_DIR', None)
    snaps = []
    for _ in range(2):
        done = subprocess.run([sys.executable, '-c', code], env=env,
                              cwd=REPO, capture_output=True, text=True,
                              timeout=300)
        assert done.returncode == 0, done.stderr[-2000:]
        line, = [l for l in done.stdout.splitlines()
                 if l.startswith('SNAP ')]
        snaps.append(json.loads(line[len('SNAP '):]))
    cold, warm = snaps
    assert 'compile.cache_read_secs' not in cold['h']
    assert cold['c']['compile.programs'] >= 1
    assert warm['c']['compile.cache_hits'] >= 1
    reads = warm['h']['compile.cache_read_secs']
    assert reads['count'] == warm['c']['compile.cache_hits']
    assert 0 < reads['sum'] <= warm['h']['compile.backend_secs']['sum']


def test_check_trace_accepts_a_profiled_fit_with_its_compile(tmp_path):
    """The AOT capture's compile is a ``perf.phase.compile`` span inside
    ``step_prep``, inside its ``perf.fit_step`` root, and the dump keeps
    check_trace's nesting rules."""
    perfwatch.set_enabled(True)
    instrument.set_profiling(True)
    try:
        _fit()
        path = str(tmp_path / 'setup_trace.json')
        instrument.dump_trace(path)
    finally:
        instrument.set_profiling(False)
    assert check_trace.validate_file(path) == []
    with open(path) as f:
        events = [e for e in json.load(f)['traceEvents']
                  if e.get('ph') == 'X']

    def named(name):
        return [e for e in events if e['name'] == name]

    def inside(e, outer):
        return e['tid'] == outer['tid'] and outer['ts'] <= e['ts'] and \
            e['ts'] + e['dur'] <= outer['ts'] + outer['dur']

    compiles = named('perf.phase.compile')
    assert compiles
    for e in compiles:
        prep = [p for p in named('perf.phase.step_prep') if inside(e, p)]
        assert len(prep) == 1
        assert [r for r in named('perf.fit_step') if inside(prep[0], r)]
    for name in SETUP[:3]:
        span, = named('perf.setup.' + name)
        assert span['cat'] == 'setup'
        assert not [r for r in named('perf.fit_step') if inside(span, r)]
