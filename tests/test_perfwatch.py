"""Tier-1 tests for the performance-attribution plane (ISSUE 7):
per-executable XLA cost/memory accounting, live MFU + step-phase
attribution, the device-memory ledger (alloc/donate/free with the
donated-buffer double-count guard), sampled-step sync budget, OOM
forensics, the check_trace perf-span validation, and the knobs-off
overhead guard."""
import gc
import json
import math
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import callback, instrument, perfwatch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, 'tools'))
import check_trace  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_perfwatch_state():
    """perfwatch state is process-global: restore everything so the
    rest of the suite is unaffected."""
    prof, met = instrument.profiling_enabled(), instrument.metrics_enabled()
    instrument.clear_trace()
    instrument.reset_metrics()
    perfwatch.set_enabled(False)
    perfwatch.ledger_reset()
    perfwatch.clear_executables()
    yield
    perfwatch.refresh()
    perfwatch.set_enabled(False)
    perfwatch.ledger_reset()
    perfwatch.clear_executables()
    instrument.set_profiling(prof)
    instrument.set_metrics(met)
    instrument.clear_trace()
    instrument.reset_metrics()


def _mlp(classes=4):
    net = mx.sym.Variable('data')
    net = mx.sym.FullyConnected(net, num_hidden=16, name='pfc1')
    net = mx.sym.Activation(net, act_type='relu', name='pact1')
    net = mx.sym.FullyConnected(net, num_hidden=classes, name='pfc2')
    return mx.sym.SoftmaxOutput(net, name='softmax')


def _cls_data(rng, n, d=10, classes=4):
    X = rng.randn(n, d).astype(np.float32)
    Y = (X @ rng.randn(d, classes)).argmax(1).astype(np.float32)
    return X, Y


def _fit(env, X, Y, bs, num_epoch=1, frequent=2, classes=4):
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        mx.random.seed(7)
        it = mx.io.NDArrayIter(data=X, label=Y, batch_size=bs,
                               shuffle=False)
        mod = mx.mod.Module(_mlp(classes))
        mod.fit(it, num_epoch=num_epoch, optimizer='sgd',
                optimizer_params={'learning_rate': 0.1},
                eval_metric='acc', initializer=mx.init.Uniform(0.05),
                batch_end_callback=[callback.Speedometer(bs, frequent)])
        return mod
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# ---------------------------------------------------------------------------
# MFU math + peaks
# ---------------------------------------------------------------------------

def test_mfu_math_and_peak_override(monkeypatch):
    assert perfwatch.mfu(1e12, 2.0, peak=197e12) == \
        pytest.approx(2e12 / 197e12)
    assert perfwatch.mfu(0.0, 2.0, peak=197e12) == 0.0
    assert perfwatch.mfu(1e12, 0.0, peak=197e12) == 0.0
    assert perfwatch.roofline_mandatory(1e9, 2.0, peak_bw=819e9) == \
        pytest.approx(2e9 / 819e9)
    # device-kind table: prefix match; a kind it lacks is an error
    assert perfwatch.device_peaks('TPU v5 lite chip') == \
        perfwatch.PEAKS['TPU v5 lite']
    with pytest.raises(mx.MXNetError, match='weird-accelerator'):
        perfwatch.device_peaks('weird-accelerator')
    # the MXTPU_PEAK_FLOPS override replaces the flops term only
    monkeypatch.setenv('MXTPU_PEAK_FLOPS', '5e12')
    assert perfwatch.peaks()[0] == 5e12
    assert perfwatch.mfu(1e12, 1.0) == pytest.approx(0.2)
    # without it the CPU backend has no row: no made-up host peak
    monkeypatch.delenv('MXTPU_PEAK_FLOPS')
    with pytest.raises(mx.MXNetError, match='MXTPU_PEAK_FLOPS'):
        perfwatch.peaks()


# ---------------------------------------------------------------------------
# Leg 1 + 2: executable accounting, MFU gauge, phase attribution
# ---------------------------------------------------------------------------

def test_fused_step_accounting_and_phases():
    rng = np.random.RandomState(3)
    X, Y = _cls_data(rng, 64)
    _fit({'MXTPU_PERFWATCH': '1'}, X, Y, bs=8, num_epoch=1)
    rows = perfwatch.executables()
    fit_rows = [r for r in rows if r['kind'] == 'fit_step']
    assert fit_rows, rows
    assert fit_rows[0]['flops'] > 0
    assert fit_rows[0]['output_bytes'] > 0
    snap = instrument.metrics_snapshot()
    g = snap['gauges']
    # xla.* gauges keyed by program signature
    stem = 'xla.fit_step[%s]' % fit_rows[0]['key']
    assert g[stem + '.flops'] == fit_rows[0]['flops']
    assert g['xla.executables'] >= 1
    # live MFU from executable flops x steps/sec vs the peak table
    assert 'perf.mfu' in g
    assert g['perf.mfu'] > 0
    assert g['perf.steps_per_sec'] > 0
    assert g['perf.step_flops'] == fit_rows[0]['flops']
    # device-memory ledger exported
    assert g['mem.peak_bytes'] > 0
    # per-phase attribution histograms around the existing seams
    hists = snap.get('histograms') or {}
    assert 'perf.phase.dispatch' in hists
    assert hists['perf.phase.dispatch']['count'] >= 8
    assert 'perf.phase.metric_drain' in hists
    # zero sampled syncs without MXTPU_STEP_SAMPLE
    assert snap['counters'].get('perf.host_syncs', 0) == 0


def test_bucket_table_accounting():
    """Every bucket's fused program registers its own executable row
    (distinct batch signatures -> distinct keys)."""
    rng = np.random.RandomState(5)
    num_classes = 4

    def bucket_batches():
        # bucket key = row count (the pow2-bucket serving pattern):
        # per-bucket input shapes differ, parameters are shared
        batches = []
        for key in (4, 8):
            X = rng.randn(key, 10).astype(np.float32)
            Y = rng.randint(0, num_classes, key).astype(np.float32)
            batches.append(mx.io.DataBatch(
                [mx.nd.array(X)], [mx.nd.array(Y)], pad=0,
                bucket_key=key,
                provide_data=[('data', (key, 10))],
                provide_label=[('softmax_label', (key,))]))
        return batches

    class _It(mx.io.DataIter):
        def __init__(self):
            super().__init__()
            self.batch_size = 8
            self._batches = bucket_batches()
            self._i = 0
            self.default_bucket_key = 8
            self.provide_data = [('data', (8, 10))]
            self.provide_label = [('softmax_label', (8,))]

        def reset(self):
            self._i = 0

        def next(self):
            if self._i >= len(self._batches):
                raise StopIteration
            b = self._batches[self._i]
            self._i += 1
            return b

    def sym_gen(bucket_key):
        data = mx.sym.Variable('data')
        net = mx.sym.FullyConnected(data, num_hidden=8, name='bfc1')
        net = mx.sym.Activation(net, act_type='relu', name='bact1')
        net = mx.sym.FullyConnected(net, num_hidden=num_classes,
                                    name='bfc2')
        net = mx.sym.SoftmaxOutput(net, name='softmax')
        return net, ('data',), ('softmax_label',)

    os.environ['MXTPU_PERFWATCH'] = '1'
    try:
        mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=8)
        mod.fit(_It(), num_epoch=1, optimizer='sgd',
                optimizer_params={'learning_rate': 0.1},
                eval_metric='acc', initializer=mx.init.Uniform(0.05))
    finally:
        os.environ.pop('MXTPU_PERFWATCH', None)
    keys = {r['key'] for r in perfwatch.executables()
            if r['kind'] == 'fit_step'}
    assert len(keys) >= 2, perfwatch.executables()


def test_predictor_bucket_executables_registered():
    """Each pow2 Predictor bucket executor registers its own
    'forward' executable row — and keeps serving identical outputs
    through the captured AOT path."""
    perfwatch.set_enabled(True)
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable('data'), num_hidden=4,
                              name='qfc'), name='softmax')
    params = {'arg:qfc_weight': mx.nd.array(np.ones((4, 10), np.float32)),
              'arg:qfc_bias': mx.nd.array(np.zeros((4,), np.float32))}
    p = mx.predictor.Predictor(net, params, {'data': (8, 10)},
                               pad_to_bucket=True)
    p.forward(data=np.ones((3, 10), np.float32))   # bucket 4
    out1 = p.get_output(0)
    p.forward(data=np.ones((7, 10), np.float32))   # bucket 8
    rows = [r for r in perfwatch.executables() if r['kind'] == 'forward']
    assert len({r['key'] for r in rows}) >= 2, rows
    assert all(r['flops'] > 0 for r in rows)
    p.forward(data=np.ones((3, 10), np.float32))   # cached AOT path
    assert np.allclose(p.get_output(0), out1)


def test_executable_row_recorded_into_manifest(tmp_path, monkeypatch):
    """register_executable files its cost/memory row into the warmup
    manifest (when a compile-cache dir is installed) so a later
    process knows the cost model before compiling."""
    from mxnet_tpu import compile_cache
    assert compile_cache.record_entry({'kind': 'xla_cost'}) is False \
        or compile_cache.cache_dir()    # no cache dir => no-op
    m = compile_cache._Manifest(str(tmp_path / 'manifest.json'))
    monkeypatch.setattr(compile_cache, '_manifest', m)

    class _FakeMem(object):
        argument_size_in_bytes = 10
        output_size_in_bytes = 4
        temp_size_in_bytes = 2
        alias_size_in_bytes = 0
        generated_code_size_in_bytes = 1

    class _FakeCompiled(object):
        def cost_analysis(self):
            return {'flops': 123.0, 'bytes accessed': 7.0}

        def memory_analysis(self):
            return _FakeMem()

    instrument.set_metrics(True)
    info = perfwatch.register_executable('fit_step', 'sig-x',
                                         _FakeCompiled())
    assert info['flops'] == 123.0 and info['temp_bytes'] == 2
    entries = compile_cache.manifest_entries('xla_cost')
    assert any(e.get('key') == 'sig-x' and e.get('flops') == 123.0
               for e in entries)
    # the manifest file itself committed atomically and reloads
    m2 = compile_cache._Manifest(str(tmp_path / 'manifest.json'))
    assert any(e.get('key') == 'sig-x' for e in m2.entries('xla_cost'))


# ---------------------------------------------------------------------------
# Leg 3: device-memory ledger
# ---------------------------------------------------------------------------

def test_ledger_alloc_free_and_donate_guard():
    perfwatch.set_enabled(True)
    perfwatch.ledger_reset()
    a = mx.nd.array(np.ones((256, 4), np.float32))   # 4096 bytes
    b = mx.nd.array(np.ones((128, 2), np.float32))   # 1024 bytes
    stats = perfwatch.ledger_stats()
    assert stats['live_bytes'] == 4096 + 1024
    assert stats['peak_bytes'] == 4096 + 1024
    top = perfwatch.ledger_top()
    assert top[0][0] == 'nd.array' and top[0][1] == 5120
    # GC free: dropping the array retires its bytes
    frees0 = instrument.counter('mem.frees').value
    del b
    gc.collect()
    assert perfwatch.ledger_stats()['live_bytes'] == 4096
    assert instrument.counter('mem.frees').value == frees0 + 1
    # peak is a high-water mark, not live
    assert perfwatch.ledger_stats()['peak_bytes'] == 5120
    # donation retires NOW; the later GC finalizer must not
    # double-count (the donated-buffer guard)
    handle = a.handle
    perfwatch.ledger_donate(handle)
    assert perfwatch.ledger_stats()['live_bytes'] == 0
    assert instrument.counter('mem.donations').value == 1
    frees1 = instrument.counter('mem.frees').value
    del a, handle
    gc.collect()
    assert perfwatch.ledger_stats()['live_bytes'] == 0, \
        'donated buffer double-counted on GC'
    assert instrument.counter('mem.frees').value == frees1
    # unknown arrays no-op
    perfwatch.ledger_donate(object())


def test_ledger_off_no_tracking():
    perfwatch.set_enabled(False)
    perfwatch.ledger_reset()
    a = mx.nd.array(np.ones((64,), np.float32))
    assert perfwatch.ledger_stats()['live_bytes'] == 0
    del a


# ---------------------------------------------------------------------------
# Sampled-step sync budget
# ---------------------------------------------------------------------------

def test_sampled_step_sync_budget():
    """MXTPU_STEP_SAMPLE=N costs exactly ceil(steps/N) perf syncs and
    changes metric.host_syncs not at all."""
    rng = np.random.RandomState(11)
    X, Y = _cls_data(rng, 64)          # 8 batches of 8

    _fit({'MXTPU_PERFWATCH': '1'}, X, Y, bs=8, num_epoch=1)
    base = instrument.metrics_snapshot()['counters']
    base_metric_syncs = base.get('metric.host_syncs', 0)
    assert base.get('perf.host_syncs', 0) == 0

    instrument.reset_metrics()
    perfwatch.clear_executables()
    _fit({'MXTPU_PERFWATCH': '1', 'MXTPU_STEP_SAMPLE': '3'},
         X, Y, bs=8, num_epoch=1)
    snap = instrument.metrics_snapshot()['counters']
    assert snap.get('metric.host_syncs', 0) == base_metric_syncs
    assert snap.get('perf.host_syncs', 0) == math.ceil(8 / 3)
    hist = instrument.metrics_snapshot()['histograms']
    assert hist['perf.step_latency']['count'] == math.ceil(8 / 3)


def test_sampled_step_trace_has_phase_children(tmp_path):
    """Under profiling, every sampled step emits a perf.step span with
    phase children inside — and check_trace accepts the dump."""
    rng = np.random.RandomState(13)
    X, Y = _cls_data(rng, 32)
    instrument.set_profiling(True)
    try:
        _fit({'MXTPU_PERFWATCH': '1', 'MXTPU_STEP_SAMPLE': '2'},
             X, Y, bs=8, num_epoch=1)
        path = str(tmp_path / 'perf_trace.json')
        instrument.dump_trace(path)
    finally:
        instrument.set_profiling(False)
    assert check_trace.validate_file(path) == []
    with open(path) as f:
        events = json.load(f)['traceEvents']
    steps = [e for e in events if e.get('name') == 'perf.step']
    assert len(steps) == math.ceil(4 / 2)
    assert any(e.get('name', '').startswith('perf.phase.')
               for e in events)


def test_check_trace_rejects_childless_perf_step(tmp_path):
    bad = {'traceEvents': [
        {'name': 'perf.step', 'ph': 'X', 'pid': 1, 'tid': 1,
         'ts': 1000, 'dur': 500},
        {'name': 'perf.phase.dispatch', 'ph': 'X', 'pid': 1, 'tid': 2,
         'ts': 1100, 'dur': 100},   # other thread: not a child
    ]}
    p = tmp_path / 'bad.json'
    p.write_text(json.dumps(bad))
    errors = check_trace.validate_file(str(p))
    assert errors and 'perf.step' in errors[0]
    good = {'traceEvents': [
        {'name': 'perf.step', 'ph': 'X', 'pid': 1, 'tid': 1,
         'ts': 1000, 'dur': 500},
        {'name': 'perf.phase.device_wait', 'ph': 'X', 'pid': 1,
         'tid': 1, 'ts': 1100, 'dur': 100},
    ]}
    p2 = tmp_path / 'good.json'
    p2.write_text(json.dumps(good))
    assert check_trace.validate_file(str(p2)) == []
    # a perf-plane event that is not a complete span is malformed
    nonx = {'traceEvents': [
        {'name': 'perf.phase.dispatch', 'ph': 'B', 'pid': 1, 'tid': 1,
         'ts': 1000}]}
    p3 = tmp_path / 'nonx.json'
    p3.write_text(json.dumps(nonx))
    assert check_trace.validate_file(str(p3))


# ---------------------------------------------------------------------------
# The fit iteration's span tree on the profiler's clock (ISSUE 26)
# ---------------------------------------------------------------------------

ROOT = 'mxtpu.perf.fit_step'
# the root's children on the fit thread, and the feed thread's own
IN_ROOT = ('feed_wait', 'step_prep', 'dispatch', 'step_commit', 'window_wait',
           'callbacks')
ON_FEED = ('feed_fetch', 'feed_stage')


def _traced_fit(tmp_path, batches=8, epochs=2):
    """A small ``Module.fit`` under ``jax.profiler`` with the plane on.
    Returns the ``mxtpu.`` spans of ``/host:CPU`` as
    ``{line index: [(name without the prefix, start, end, stats)]}`` and
    the metrics snapshots taken round the fit."""
    import glob
    import warnings
    import jax
    rng = np.random.RandomState(17)
    X, Y = _cls_data(rng, 8 * batches)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    perfwatch.set_enabled(True)
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(3):
            with perfwatch.phase('device_wait'):
                pass
        before = instrument.metrics_snapshot()
        _fit({'MXTPU_PERFWATCH': '1'}, X, Y, bs=8, num_epoch=epochs)
        after = instrument.metrics_snapshot()
    finally:
        jax.profiler.stop_trace()
        perfwatch.set_enabled(False)
    path, = glob.glob(str(tmp_path / '**' / '*.xplane.pb'), recursive=True)
    profile = jax.profiler.ProfileData.from_file(path)
    lines = {}
    with warnings.catch_warnings():
        warnings.simplefilter('ignore', DeprecationWarning)
        for plane in profile.planes:
            if plane.name != '/host:CPU':
                # the program's spans are host spans and nothing else
                assert not any(e.name.startswith('mxtpu.')
                               for line in plane.lines
                               for e in line.events), plane.name
                continue
            for index, line in enumerate(plane.lines):
                # a full collection (perf.gc) lands on whichever thread
                # allocated: no part of the fit's tree
                found = [(e.name[len('mxtpu.'):], e.start_ns,
                          e.start_ns + e.duration_ns, dict(e.stats))
                         for e in line.events
                         if e.name.startswith('mxtpu.') and
                         e.name != 'mxtpu.perf.gc']
                if found:
                    lines[index] = sorted(found, key=lambda s: s[1])
    return lines, before, after


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(span, outer):
    return outer[1] <= span[1] and span[2] <= outer[2]


def test_profiler_trace_holds_the_fit_span_tree(tmp_path):
    """While a jax.profiler session runs, every hist_span of a live
    plane is an ``mxtpu.``-prefixed annotation on /host:CPU, on the
    line of the thread that did the work: the root of each iteration
    with its step number, each child inside its root on the fit
    thread, the feed spans on the feed thread, the epoch's end outside
    any root."""
    lines, _, _ = _traced_fit(tmp_path)
    fit_line, = [i for i, spans in lines.items()
                 if _named(spans, 'perf.fit_step')]
    fit = lines[fit_line]
    roots = _named(fit, 'perf.fit_step')
    # the ask that found the iterator exhausted, once an epoch, cannot be
    # withdrawn from the trace: it is marked, and counts nowhere
    stubs = [r for r in roots if r[3].get('cancelled')]
    assert [r[3]['step_num'] for r in stubs] == [8, 16]
    roots = [r for r in roots if r not in stubs]
    assert [r[3]['step_num'] for r in roots] == list(range(16))
    assert len(_named(fit, 'perf.phase.device_wait')) == 3
    for name in IN_ROOT:
        children = _named(fit, 'perf.phase.' + name)
        assert children, name
        for child in children:
            holders = [r for r in roots if _inside(child, r)]
            if name == 'window_wait' and not holders:
                # the epoch's end drains the window too
                assert any(_inside(child, e)
                           for e in _named(fit, 'perf.phase.epoch_end'))
                continue
            if name == 'feed_wait' and not holders:
                # the wait that ended in "no more batches"
                assert any(_inside(child, r) for r in stubs)
                continue
            assert len(holders) == 1, (name, child)
    # one of each a root, in the loop's order; an epoch's first root has
    # no window wait (nothing is in flight yet)
    for number, root in enumerate(roots):
        order = [s[0].rsplit('.', 1)[1] for s in fit
                 if s is not root and _inside(s, root) and
                 s[0].rsplit('.', 1)[1] in IN_ROOT]
        assert order == [name for name in IN_ROOT
                         if name != 'window_wait' or number % 8], order
    # the Speedometer's drain nests inside the callbacks that ran it
    drains = _named(fit, 'perf.phase.metric_drain')
    assert drains
    for drain in drains:
        assert any(_inside(drain, c)
                   for c in _named(fit, 'perf.phase.callbacks') +
                   _named(fit, 'perf.phase.epoch_end')), drain
    ends = _named(fit, 'perf.phase.epoch_end')
    assert len(ends) == 2
    for end in ends:
        assert not any(r[1] < end[2] and r[2] > end[1] for r in roots)
    # the feed thread: its own line, parentless, and nothing of it on
    # the fit thread
    feed_line, = [i for i, spans in lines.items() if i != fit_line]
    for name in ON_FEED:
        assert _named(lines[feed_line], 'perf.phase.' + name), name
        assert not _named(fit, 'perf.phase.' + name)
    assert not _named(lines[feed_line], 'perf.fit_step')
    assert {s[0] for s in lines[feed_line]} == \
        {'perf.phase.' + name for name in ON_FEED}


def test_fit_step_roots_count_cover_and_tile(tmp_path):
    """Per step the children's sum does not exceed the root's length,
    the root's histogram counts what fit.batches counts, and the roots
    of an epoch tile it: their sum is within 2% of first start to last
    end."""
    lines, before, after = _traced_fit(tmp_path, batches=16)
    fit, = [spans for spans in lines.values()
            if _named(spans, 'perf.fit_step')]
    roots = [r for r in _named(fit, 'perf.fit_step')
             if not r[3].get('cancelled')]

    def moved(kind, name, field=None):
        new, old = after[kind].get(name), before[kind].get(name)
        if field is None:
            return new - (old or 0)
        return new[field] - (old[field] if old else 0)

    assert moved('counters', 'fit.batches') == 32 == len(roots)
    assert moved('histograms', 'perf.fit_step', 'count') == 32
    assert moved('histograms', 'perf.phase.step_prep', 'count') == 32
    assert moved('histograms', 'perf.phase.callbacks', 'count') == 32
    assert moved('histograms', 'perf.phase.epoch_end', 'count') == 2
    for root in roots:
        children = sum(s[2] - s[1] for s in fit
                       if s[0].rsplit('.', 1)[1] in IN_ROOT and
                       _inside(s, root))
        assert 0 < children <= root[2] - root[1]
    # the same on the histograms' own clock, over the whole fit
    assert sum(moved('histograms', 'perf.phase.' + name, 'sum')
               for name in IN_ROOT if name != 'window_wait') <= \
        moved('histograms', 'perf.fit_step', 'sum')
    for epoch in (roots[:16], roots[16:]):
        covered = sum(r[2] - r[1] for r in epoch)
        extent = epoch[-1][2] - epoch[0][1]
        assert covered <= extent
        assert covered >= 0.98 * extent, (covered, extent)


def test_planes_off_enter_no_profiler_annotation(monkeypatch):
    """With every plane off the seam hands out the shared no-op: a fit
    builds and enters no jax.profiler annotation at all."""
    import jax

    def refuse(*args, **kwargs):
        raise AssertionError('a profiler annotation with the planes off')

    monkeypatch.setattr(jax.profiler, 'TraceAnnotation', refuse)
    monkeypatch.setattr(jax.profiler, 'StepTraceAnnotation', refuse)
    assert perfwatch.phase('dispatch') is instrument.NULL_CTX
    assert perfwatch.fit_step(3) is instrument.NULL_CTX
    rng = np.random.RandomState(19)
    X, Y = _cls_data(rng, 32)
    _fit({}, X, Y, bs=8, num_epoch=2)
    instrument.set_metrics(True)        # the registry alone is no plane
    try:
        _fit({}, X, Y, bs=8, num_epoch=1)
        hist = instrument.metrics_snapshot().get('histograms') or {}
        assert not [k for k in hist if k.startswith('perf.')]
    finally:
        instrument.set_metrics(False)
    # and with the plane on, the same patch is reached
    perfwatch.set_enabled(True)
    with pytest.raises(AssertionError):
        perfwatch.phase('dispatch')


def test_fused_step_names_its_nodes_in_op_name():
    """jax.named_scope round every operator and round the step's three
    parts: the node's name is in the op_name of its forward and of its
    backward ops, in the lowered text and in the compiled HLO, and the
    text without debug info (what the fuse pins compare) has none of
    it."""
    import re
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.parallel.train_step import (make_fit_step,
                                               make_sgd_momentum,
                                               _PlainUpdate)
    net = mx.sym.Variable('data')
    net = mx.sym.Convolution(net, num_filter=4, kernel=(3, 3), pad=(1, 1),
                             name='sconv0')
    net = mx.sym.BatchNorm(net, name='sbn0')
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2),
                         pool_type='max', name='spool0')
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=10,
                                name='sfc')
    net = mx.sym.SoftmaxOutput(net, name='softmax')
    arg_shapes, _, aux_shapes = net.infer_shape(data=(4, 3, 8, 8))
    vals = {n: jnp.ones(s, jnp.float32)
            for n, s in zip(net.list_arguments(), arg_shapes)}
    aux = {n: jnp.ones(s, jnp.float32)
           for n, s in zip(net.list_auxiliary_states(), aux_shapes)}
    raw = make_fit_step(net, _PlainUpdate(make_sgd_momentum(
        lr=0.05, momentum=0.9, wd=0.0, rescale_grad=0.25)),
        data_names=(), _raw=True,
        metric_fn=lambda label, pred: {'n': jnp.sum(label)},
        metric_label='softmax_label')
    batch = {k: vals.pop(k) for k in ('data', 'softmax_label')}
    opt = {k: jnp.zeros_like(v) for k, v in vals.items()}

    def step(params, aux, opt, metric, batch, rng):
        return raw(params, {}, aux, opt, metric, batch, jnp.float32(0.1),
                   rng)

    lowered = jax.jit(step).lower(vals, aux, opt, {'n': jnp.float32(0)},
                                  batch, jax.random.PRNGKey(0))
    assert 'spool0' not in lowered.as_text()
    located = set(re.findall(r'loc\("([^"]*)"',
                             lowered.as_text(debug_info=True)))
    compiled = set(re.findall(r'op_name="([^"]*)"',
                              lowered.compile().as_text()))
    for names in (located, compiled):
        for node in ('Pooling/spool0', 'BatchNorm/sbn0',
                     'Convolution/sconv0'):
            forward = [n for n in names
                       if node in n and 'transpose(' not in n]
            backward = [n for n in names
                        if node in n and 'transpose(' in n]
            assert forward and backward, node
            assert all('forward_backward/' in n
                       for n in forward + backward)
        assert any('/optimizer/' in n for n in names)
        assert any('/metric/' in n for n in names)


def test_check_trace_learns_the_fit_step_root(tmp_path):
    """A perf.fit_step root contains the phases that meet it on its
    thread, and roots do not overlap; a real profiled fit passes."""
    def span(name, ts, dur, tid=1):
        return {'name': name, 'ph': 'X', 'pid': 1, 'tid': tid, 'ts': ts,
                'dur': dur}

    good = [span('perf.fit_step', 1000, 500),
            span('perf.phase.feed_wait', 1000, 10),
            span('perf.phase.callbacks', 1400, 101),    # rounding
            span('perf.phase.metric_drain', 1410, 50),
            span('perf.fit_step', 1500, 500),
            span('perf.phase.dispatch', 1600, 100),
            span('perf.phase.epoch_end', 2100, 50),     # outside any root
            span('perf.phase.feed_stage', 1400, 300, tid=2)]
    assert check_trace.validate_events(good) == []
    straddling = good + [span('perf.phase.window_wait', 1450, 100)]
    errors = check_trace.validate_events(straddling)
    assert len(errors) == 2 and all('sticks out' in e for e in errors)
    early = good + [span('perf.phase.window_wait', 900, 200)]
    assert 'sticks out' in check_trace.validate_events(early)[0]
    overlapping = good + [span('perf.fit_step', 1900, 300)]
    assert 'overlap' in check_trace.validate_events(overlapping)[0]
    begun = [{'name': 'perf.fit_step', 'ph': 'B', 'pid': 1, 'tid': 1,
              'ts': 1000}]
    assert check_trace.validate_events(begun)

    rng = np.random.RandomState(23)
    X, Y = _cls_data(rng, 32)
    instrument.set_profiling(True)
    try:
        _fit({'MXTPU_PERFWATCH': '1'}, X, Y, bs=8, num_epoch=2)
        path = str(tmp_path / 'root_trace.json')
        instrument.dump_trace(path)
    finally:
        instrument.set_profiling(False)
    assert check_trace.validate_file(path) == []
    with open(path) as f:
        events = json.load(f)['traceEvents']
    roots = [e for e in events if e.get('name') == 'perf.fit_step']
    assert len(roots) == 8
    for root in roots:
        inside = [e['name'] for e in events
                  if e.get('tid') == root['tid'] and
                  e.get('name', '').startswith('perf.phase.') and
                  root['ts'] <= e['ts'] and
                  e['ts'] + e['dur'] <= root['ts'] + root['dur'] + 2]
        for name in ('step_prep', 'dispatch', 'step_commit'):
            assert 'perf.phase.' + name in inside


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------

_OOM_SCRIPT = r"""
import json, os, sys
os.environ.setdefault('JAX_PLATFORMS', 'cpu')
os.environ['MXTPU_PERFWATCH'] = '1'
os.environ['MXTPU_FLIGHT_RECORDER'] = sys.argv[1]
import numpy as np
import mxnet_tpu as mx

rng = np.random.RandomState(0)
X = rng.randn(16, 10).astype(np.float32)
Y = (X @ rng.randn(10, 4)).argmax(1).astype(np.float32)
it = mx.io.NDArrayIter(data=X, label=Y, batch_size=8, shuffle=False)
net = mx.sym.Variable('data')
net = mx.sym.FullyConnected(net, num_hidden=8, name='ofc1')
net = mx.sym.SoftmaxOutput(net, name='softmax')
mod = mx.mod.Module(net)
mod.fit(it, num_epoch=1, optimizer='sgd',
        optimizer_params={'learning_rate': 0.1}, eval_metric='acc',
        initializer=mx.init.Uniform(0.05))

# inject a RESOURCE_EXHAUSTED at the fused dispatch site: the already-
# registered executable for this batch signature must be named in the
# postmortem
err = RuntimeError('RESOURCE_EXHAUSTED: Out of memory while trying to '
                   'allocate 34359738368 bytes')
mod._fused_aot.clear()
mod._fused_aot_pending.clear()
mod._perf_aot_failed = set()


def boom(*a, **k):
    raise err


mod._fused = boom
it.reset()
batch = it.next()
try:
    mod._run_fused(batch)
except RuntimeError as e:
    assert 'RESOURCE_EXHAUSTED' in str(e)
else:
    raise SystemExit('injected OOM did not propagate')
print('INJECTED-OK')
"""


def test_oom_forensics_subprocess(tmp_path):
    env = dict(os.environ)
    env.pop('MXTPU_PROFILE', None)
    env.pop('MXTPU_METRICS', None)
    env['JAX_PLATFORMS'] = 'cpu'
    proc = subprocess.run(
        [sys.executable, '-c', _OOM_SCRIPT, str(tmp_path)],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert 'INJECTED-OK' in proc.stdout, proc.stdout
    # the postmortem must survive the process death it explains: the
    # atexit 'exit' dump overwrites flightrec-rank0.json, but the
    # reason-suffixed record is durable
    with open(str(tmp_path / 'flightrec-rank0-oom.json')) as f:
        doc = json.load(f)
    assert doc['reason'] == 'oom'
    oom = doc['oom']
    # names the triggering executable, with its compile-time analysis
    assert oom['executable']['kind'] == 'fit_step'
    assert oom['executable']['flops'] > 0
    assert 'RESOURCE_EXHAUSTED' in oom['error']
    # top live buffers from the ledger
    assert oom['ledger']['top'], oom['ledger']
    assert oom['ledger']['peak_bytes'] > 0
    assert any(row['site'] == 'io.h2d' for row in oom['ledger']['top'])
    # current perf picture rides along
    assert 'perf.mfu' in oom['perf']


def test_on_error_ignores_non_oom():
    perfwatch.set_enabled(True)
    assert perfwatch.on_error(ValueError('shape mismatch'),
                              'fit_step', 'k') is None
    assert not perfwatch.is_oom(ValueError('shape mismatch'))
    assert perfwatch.is_oom(RuntimeError('RESOURCE_EXHAUSTED: ...'))
    assert perfwatch.is_oom(RuntimeError('Out of memory allocating'))


# ---------------------------------------------------------------------------
# Off-path overhead guard
# ---------------------------------------------------------------------------

_FLOOR_ON = False


def _floor_hook(a=None, b=None):
    """The inlined ideal off path: one module-global flag check (same
    signature shape as the real hooks so argument plumbing cancels)."""
    if not _FLOOR_ON:
        return None


def test_knobs_off_overhead_guard():
    """With MXTPU_PERFWATCH off, every hot-path hook must stay
    single-check cheap: < 2x a same-shape inlined ideal floor, so
    future call sites cannot make the off path allocate or chase
    attributes.  Floor and hook are measured adjacently per pair to
    damp CI-box noise."""
    perfwatch.set_enabled(False)
    assert not perfwatch.enabled()
    n = 20000

    def measure(fn):
        best = float('inf')
        for _ in range(7):
            t0 = time.perf_counter()
            for _ in range(n):
                fn()
            best = min(best, time.perf_counter() - t0)
        return best

    pairs = (
        ('sample_tick', lambda: perfwatch.sample_tick(),
         lambda: _floor_hook()),
        ('phase', lambda: perfwatch.phase('dispatch'),
         lambda: _floor_hook('dispatch')),
        ('fit_step', lambda: perfwatch.fit_step(7),
         lambda: _floor_hook(7)),
        ('note_step', lambda: perfwatch.note_step('fit_step', None),
         lambda: _floor_hook('fit_step', None)),
        ('ledger_alloc', lambda: perfwatch.ledger_alloc('s', None),
         lambda: _floor_hook('s', None)),
        ('ledger_donate', lambda: perfwatch.ledger_donate(None),
         lambda: _floor_hook(None)),
    )
    worst = []
    for name, hook, floor_fn in pairs:
        ratio = min((measure(hook) + 0.0) / max(measure(floor_fn), 1e-9)
                    for _ in range(3))      # best-of-3 damps noise
        worst.append((name, ratio))
    for name, ratio in worst:
        assert ratio < 2.0, \
            ('%s off-path is %.2fx its floor (all: %s)'
             % (name, ratio, worst))
    assert instrument.trace_events() == []
