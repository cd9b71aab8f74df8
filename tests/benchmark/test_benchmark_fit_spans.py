"""The five per-layer metrics that read the program's own spans (PR 26):
their files against the manifest, the two data-defined ones on hand-made
snapshots, the three readers on a hand-made profile, and the traced
rehearsal, in which none of them may appear and none may raise."""
import collections
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, manifest  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.readers import fit_span_tree  # noqa: E402

SPEC = manifest.load_manifest()
FIT_CELLS = ['resnet50_fit_hostfeed', 'inception_v3_fit_hostfeed',
             'resnet50_fit_dp4']
NEW = {'fit_host_step_ms': ('program_span', 'fit loop'),
       'fit_step_prep_ms': ('program_span', 'fit loop'),
       'fit_callback_ms': ('program_span', 'fit loop'),
       'fit_feed_stage_ms': ('program_span', 'input feed'),
       'fit_idle_host_ms': ('device_trace', 'fit loop')}

Event = collections.namedtuple('Event', 'name start_ns duration_ns')
Line = collections.namedtuple('Line', 'name events')
Plane = collections.namedtuple('Plane', 'name lines')
Profile = collections.namedtuple('Profile', 'planes')

MS = 1000000
P = 'mxtpu.perf.phase.'


def ms(name, start, end):
    return Event(name, int(round(start * MS)), int(round((end - start) * MS)))


@pytest.mark.parametrize('name', sorted(NEW))
def test_the_five_are_appended_for_the_fit_cells(name):
    source, layer = NEW[name]
    entry, = [m for m in SPEC['per_layer'] if m['name'] == name]
    assert entry == {'name': name, 'unit': 'ms/step', 'better': 'lower',
                     'source': source, 'layer': layer,
                     'moves': 'fit_samples_per_s', 'workloads': FIT_CELLS}
    # appended: after the eight that were there
    assert [m['name'] for m in SPEC['per_layer']].index(name) >= 8
    body = manifest.load_layer_metric(name)
    assert body['drivers'] == ['fit'] and len(body['what']) > 40
    assert layer in {m['layer'] for m in SPEC['per_layer'][:8]}


def snapshot(**histograms):
    return {'counters': {}, 'histograms': {
        name.replace('_', '.', 2): {'sum': total, 'count': count}
        for name, (total, count) in histograms.items()}}


def test_the_two_data_defined_metrics_on_hand_made_snapshots():
    before = snapshot(perf_phase_step_prep=(1.0, 100),
                      perf_phase_feed_fetch=(0.5, 100),
                      perf_phase_feed_stage=(2.0, 100))
    after = snapshot(perf_phase_step_prep=(1.05, 120),
                     perf_phase_feed_fetch=(0.52, 120),
                     perf_phase_feed_stage=(2.30, 120))
    slice_ = {'snap0': before, 'snap1': after, 'steps': 20.0}
    prep = harness.evaluate(manifest.load_layer_metric('fit_step_prep_ms'),
                            slice_)
    assert prep == pytest.approx(0.05 / 20 * 1000)
    stage = harness.evaluate(manifest.load_layer_metric('fit_feed_stage_ms'),
                             slice_)
    assert stage == pytest.approx((0.02 + 0.30) / 20 * 1000)
    # a program from before PR 26 never wrote the histograms: left out
    old = {'snap0': snapshot(), 'snap1': snapshot(), 'steps': 20.0}
    for name in ('fit_step_prep_ms', 'fit_feed_stage_ms'):
        assert harness.evaluate(manifest.load_layer_metric(name), old) is None
    # one of the feed's two is not enough
    half = {'snap0': snapshot(), 'steps': 20.0,
            'snap1': snapshot(perf_phase_feed_stage=(2.0, 100))}
    assert harness.evaluate(manifest.load_layer_metric('fit_feed_stage_ms'),
                            half) is None


def root(start, end, wait_from, wait_to, prep, dispatch, bench=None,
         drain=None):
    """One iteration on the fit thread: feed_wait 0.1 ms, step_prep and
    dispatch as long as given, window_wait from and to, callbacks over
    the last 0.4 ms with the benchmark's callback and the program's
    drain inside them."""
    events = [ms('mxtpu.perf.fit_step', start, end),
              ms(P + 'feed_wait', start, start + 0.1),
              ms(P + 'step_prep', start + 0.1, start + 0.1 + prep),
              ms(P + 'dispatch', start + 0.1 + prep,
                 start + 0.1 + prep + dispatch),
              ms(P + 'window_wait', wait_from, wait_to),
              ms(P + 'callbacks', end - 0.4, end)]
    if bench:
        events.append(ms('bench.batch_end', *bench))
    if drain:
        events.append(ms(P + 'metric_drain', *drain))
    return events


def hand_made(device=True, roots=True):
    """A slice from 1 to 11 ms.  Three whole iterations and one that the
    slice's end cuts (a real trace never holds that one: the profiler
    drops a span that is open when it stops; the helper clips what it is
    given).  Chip 0 is idle four times: 0.5 ms under the first
    step_prep, 0.4 ms under the feed thread's feed_stage, 0.3 ms under
    a window_wait alone, and 0.05 ms, too short to name."""
    fit = [ms('bench.slice', 1.0, 11.0)]
    if roots:
        fit += root(1.2, 4.0, 2.0, 3.5, prep=0.5, dispatch=0.2,
                    bench=(3.7, 3.9))
        fit += root(4.0, 7.0, 4.8, 6.5, prep=0.4, dispatch=0.3,
                    bench=(6.6, 6.65), drain=(6.7, 6.9))
        fit += root(7.0, 10.0, 7.7, 9.5, prep=0.5, dispatch=0.1,
                    bench=(9.7, 9.8))
        fit += [ms('mxtpu.perf.fit_step', 10.0, 12.0),
                ms(P + 'feed_wait', 10.0, 10.1),
                ms(P + 'step_prep', 10.1, 10.6),
                ms(P + 'window_wait', 10.6, 11.5)]
    else:
        fit += [ms('bench.batch_end', 3.7, 3.9)]
    feed = [ms(P + 'feed_fetch', 1.0, 1.1), ms('bench.iter_next', 1.0, 1.05),
            ms(P + 'feed_stage', 4.15, 4.65)] if roots else \
        [ms('bench.iter_next', 1.0, 1.05)]
    planes = [Plane('/host:CPU', [Line('python3', fit),
                                  Line('python3', feed)])]
    if device:
        op = '%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %a), kind=kLoop'
        busy = [(0.5, 1.5), (2.0, 4.2), (4.6, 5.0), (5.3, 8.0),
                (8.05, 11.5)]
        planes.append(Plane('/device:TPU:0', [Line(
            'XLA Ops', [ms(op, a, b) for a, b in busy])]))
    return Profile(planes)


def test_the_tree_of_a_hand_made_profile():
    tree = fit_span_tree.tree_of(hand_made())
    assert tree.window == (1 * MS, 11 * MS)
    assert [(s / MS, e / MS) for s, e, _ in tree.roots] == [
        (1.2, 4.0), (4.0, 7.0), (7.0, 10.0), (10.0, 11.0)]   # the last cut
    assert tree.coverage == pytest.approx(0.98)
    # the benchmark's spans on the fit thread only: not its iterator's
    assert sorted(n for _, _, n in tree.bench) == ['bench.batch_end'] * 3
    # roots 9.8 ms; waits 1.6 + 1.8 + 1.9 + 0.5; the benchmark's 0.35
    assert tree.host_step_ms() == pytest.approx(
        (9.8 - 5.8 - 0.35) / 4)
    # callbacks 0.4 each, less the benchmark's 0.2, 0.05 and 0.1; the
    # program's own drain stays in
    assert tree.callback_ms() == pytest.approx((1.2 - 0.35) / 3)
    # each whole root leaves 0.1 ms that no child names
    assert tree.self_ms() == pytest.approx(0.3 / 4)


def test_gaps_are_named_after_the_programs_spans_of_any_thread():
    tree = fit_span_tree.tree_of(hand_made())
    assert [(a / MS, b / MS) for a, b in tree.idle] == [
        (1.5, 2.0), (4.2, 4.6), (5.0, 5.3)]          # 0.05 ms is no gap
    assert tree.named_gaps() == [
        (1.5 * MS, 2.0 * MS, P + 'step_prep'),
        (4.2 * MS, 4.6 * MS, P + 'feed_stage'),
        (5.0 * MS, 5.3 * MS, tr.UNATTRIBUTED)]
    assert tree.under(1.5 * MS, 2.0 * MS) == 'step_prep 60%, dispatch 40%'
    assert tree.under(5.0 * MS, 5.3 * MS) == 'window_wait 100%'
    # the gap under window_wait alone is inside the program: waiting for
    # the device explains no gap of the device
    assert tree.idle_host_ms(20.0) == pytest.approx(0.9 / 20)


def test_no_tpu_plane_or_no_root_gives_no_tree():
    assert fit_span_tree.tree_of(hand_made(device=False)) is None
    assert fit_span_tree.tree_of(hand_made(roots=False)) is None
    no_slice = Profile([Plane('/device:TPU:0', [Line('XLA Ops', [])])])
    assert fit_span_tree.tree_of(no_slice) is None


def test_the_readers_find_the_newest_trace_and_log_each_gap(
        tmp_path, monkeypatch, capsys):
    older = tmp_path / 'cell_a' / 'plugins' / 'profile' / 'x'
    newer = tmp_path / 'cell_b' / 'plugins' / 'profile' / 'y'
    for folder, stamp in ((older, 1000), (newer, 2000)):
        folder.mkdir(parents=True)
        path = folder / 'host.xplane.pb'
        path.write_bytes(b'')
        os.utime(str(path), (stamp, stamp))
    loaded = []

    def load(path):
        loaded.append(path)
        return hand_made()

    monkeypatch.setattr(harness, 'TRACE_DIR', str(tmp_path))
    monkeypatch.setattr(tr, 'load', load)
    fit_span_tree._tree_at.cache_clear()
    slice_ = {'steps': 20.0, 'trace': {}}
    values = {name: manifest.load_module('readers', name).read(slice_)
              for name in ('fit_host_step_ms', 'fit_callback_ms',
                           'fit_idle_host_ms')}
    assert values['fit_host_step_ms'] == pytest.approx(3.65 / 4)
    assert values['fit_callback_ms'] == pytest.approx(0.85 / 3)
    assert values['fit_idle_host_ms'] == pytest.approx(0.045)
    # read once for the three, and the newer of the two
    assert loaded == [str(newer / 'host.xplane.pb')]
    out = capsys.readouterr().out
    assert ('[bench] idle gap on chip 0: 0.500 ms at +0.500 ms %sstep_prep '
            '(under it: step_prep 60%%, dispatch 40%%)' % P) in out
    assert ('[bench] idle gap on chip 0: 0.300 ms at +4.000 ms '
            'inside-the-program (under it: window_wait 100%)') in out
    assert '[bench] span tree: 4 roots tile 98.00% of the 0.010 s slice' \
        in out
    # the program from before PR 26, and the rehearsal: nothing, no raise
    for profile in (hand_made(roots=False), hand_made(device=False)):
        fit_span_tree._tree_at.cache_clear()
        monkeypatch.setattr(tr, 'load', lambda path, p=profile: p)
        for name in values:
            assert manifest.load_module('readers', name).read(slice_) is None
    monkeypatch.setattr(harness, 'TRACE_DIR', str(tmp_path / 'none'))
    assert manifest.load_module('readers', 'fit_host_step_ms').read(
        slice_) is None


def test_a_traced_rehearsal_still_ends_with_its_result_line(tmp_path):
    """``--rehearse-cpu --trace 1`` of a cell that lists the five: the
    program's spans are in the trace, there is no TPU plane, so every
    reader says nothing and the line holds no time from a CPU."""
    copy = str(tmp_path / 'checkout')
    shutil.copytree(os.path.join(ROOT, 'benchmark'),
                    os.path.join(copy, 'benchmark'),
                    ignore=shutil.ignore_patterns('__pycache__'))
    shutil.copy(os.path.join(ROOT, 'BENCHMARK.json'), copy)
    os.symlink(os.path.join(ROOT, 'mxnet_tpu'),
               os.path.join(copy, 'mxnet_tpu'))
    environ = dict(os.environ, JAX_PLATFORMS='cpu',
                   JAX_COMPILATION_CACHE_DIR=os.path.join(copy, '.jax_cache'))
    done = subprocess.run(
        [sys.executable, 'benchmark/run.py', '--workload',
         'resnet50_fit_hostfeed', '--seed', str(2 ** 31 + 77), '--seconds',
         '1', '--trace', '1', '--rehearse-cpu'],
        cwd=copy, env=environ, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads([l for l in done.stdout.splitlines() if l.strip()][-1])
    assert line['rehearsal'] is True and line['correct'] is True
    assert line['metrics'] == {} and 'breakdown' not in line
    assert 'idle gap on chip 0' not in done.stdout
