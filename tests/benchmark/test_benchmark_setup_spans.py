"""The seven per-layer metrics that read the program's set-up spans and
counters and the idle time no span names: their entries against their
files, each reader on hand-made snapshots and on a hand-made profile,
and nothing (no raise) from a program or a run that lacks the series."""
import collections
import os
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
# the image cells; each language-model cell's own tests pin its per-layer
# list, so those cells are listed when those tests are relaxed
CELLS = ['resnet50_fit_hostfeed', 'inception_v3_fit_hostfeed',
         'resnet50_fit_dp4']
DRIVERS = ['fit']
SETUP = {'setup_bind_s': ('s', 'program_span'),
         'setup_trace_s': ('s', 'program_span'),
         'setup_compile_s': ('s', 'program_span'),
         'setup_cache_read_s': ('s', 'program_span'),
         'setup_gc_s': ('s', 'program_span'),
         'setup_programs': ('programs', 'program_counter')}
NEW = sorted(SETUP) + ['fit_idle_unnamed_ms']

Event = collections.namedtuple('Event', 'name start_ns duration_ns')
Line = collections.namedtuple('Line', 'name events')
Plane = collections.namedtuple('Plane', 'name lines')
Profile = collections.namedtuple('Profile', 'planes')

MS = 1000000
P = 'mxtpu.perf.phase.'


def read(name, slice_):
    return harness.evaluate(manifest.load_layer_metric(name), slice_)


@pytest.mark.parametrize('name', NEW)
def test_each_entry_agrees_with_its_file_and_lists_the_image_cells(name):
    entry, = [m for m in SPEC['per_layer'] if m['name'] == name]
    if name in SETUP:
        unit, source = SETUP[name]
        layer, moves = 'set-up', 'setup_s'
    else:
        unit, source = 'ms/step', 'device_trace'
        layer, moves = 'fit loop', 'fit_samples_per_s'
    assert entry == {'name': name, 'unit': unit, 'better': 'lower',
                     'source': source, 'layer': layer, 'moves': moves,
                     'workloads': CELLS}
    body = manifest.load_layer_metric(name)
    assert body['drivers'] == DRIVERS
    assert body['read'] == {'reader': name} and len(body['what']) > 60
    for key in ('unit', 'better', 'source', 'layer', 'moves'):
        assert body[key] == entry[key]


def test_the_seven_are_appended_after_what_was_there():
    names = [m['name'] for m in SPEC['per_layer']]
    first = names.index('setup_bind_s')
    assert names[first - 1] == 'nemo_held_share_pct'
    assert sorted(names[first:first + 7]) == sorted(NEW)
    # the layer the others name, letter for letter
    assert 'fit loop' in {m['layer'] for m in SPEC['per_layer'][:first]}


def snapshot(counters=None, **sums):
    return {'counters': dict(counters or {}), 'histograms': {
        name.replace('__', '.'): {'sum': total, 'count': 3}
        for name, total in sums.items()}}


# the set-up as a snapshot at the window's start holds it; the snapshot
# at the end holds more, which no setup_* metric may read
AT_START = snapshot(
    {'compile.programs': 42},
    perf__setup__bind=1.5, perf__setup__init_params=2.0,
    perf__setup__init_optimizer=0.5,
    compile__trace_secs=3.0, compile__lower_secs=1.0,
    compile__backend_secs=10.0, compile__cache_read_secs=2.5,
    perf__gc=0.75)
AT_END = snapshot(
    {'compile.programs': 50},
    perf__setup__bind=1.5, perf__setup__init_params=2.0,
    perf__setup__init_optimizer=0.5,
    compile__trace_secs=4.0, compile__lower_secs=2.0,
    compile__backend_secs=12.0, compile__cache_read_secs=3.5,
    perf__gc=1.75)
WANT = {'setup_bind_s': 4.0, 'setup_trace_s': 4.0, 'setup_compile_s': 7.5,
        'setup_cache_read_s': 2.5, 'setup_gc_s': 0.75,
        'setup_programs': 42.0}


def traced(snap0, snap1=None, trace=True):
    return {'snap0': snap0, 'snap1': snap1 or snap0, 'steps': 20.0,
            'trace': {'busy_s': 1.0, 'window_s': 2.0} if trace else None}


@pytest.mark.parametrize('name', sorted(SETUP))
def test_each_setup_reader_reads_the_snapshot_at_the_windows_start(name):
    assert read(name, traced(AT_START, AT_END)) == pytest.approx(WANT[name])


@pytest.mark.parametrize('name', NEW)
def test_each_reader_reads_nothing_without_its_source(name, tmp_path,
                                                      monkeypatch):
    monkeypatch.setattr(harness, 'TRACE_DIR', str(tmp_path / 'none'))
    fit_span_tree._tree_at.cache_clear()
    empty = {'snap0': {}, 'snap1': {}, 'trace': None, 'steps': 20.0}
    assert read(name, empty) is None
    # the parent's program: a chip's trace, and none of the series
    assert read(name, traced(snapshot(), snapshot())) is None
    # the CPU rehearsal: the series, and no chip's trace
    assert read(name, traced(AT_START, AT_END, trace=False)) is None


def test_a_set_up_that_read_nothing_from_the_cache_reads_zero_there():
    cold = snapshot({'compile.programs': 7}, compile__backend_secs=9.0,
                    compile__trace_secs=1.0, compile__lower_secs=0.5)
    assert read('setup_cache_read_s', traced(cold)) == 0.0
    assert read('setup_compile_s', traced(cold)) == pytest.approx(9.0)
    assert read('setup_programs', traced(cold)) == 7.0
    # no collection yet: the plane made the histogram, it reads 0
    assert read('setup_gc_s', traced(snapshot(perf__gc=0.0))) == 0.0
    # one of bind's three missing is no reading
    part = snapshot(perf__setup__bind=1.0, perf__setup__init_params=1.0)
    assert read('setup_bind_s', traced(part)) is None


def ms(name, start, end):
    return Event(name, int(round(start * MS)), int(round((end - start) * MS)))


def hand_made():
    """A slice from 1 to 10 ms of three iterations.  Chip 0 is idle three
    times for 0.1 ms or more: 0.35 ms under a full collection of Python's
    collector (``perf.gc``), 0.3 ms under a ``window_wait`` alone and
    0.4 ms under no span of the program but the root."""
    fit = [ms('bench.slice', 1.0, 10.0),
           ms('mxtpu.perf.fit_step', 1.0, 4.0),
           ms(P + 'step_prep', 1.0, 1.5),
           ms(P + 'window_wait', 2.0, 3.5),
           ms('mxtpu.perf.gc', 3.55, 3.95),
           ms('mxtpu.perf.fit_step', 4.0, 7.0),
           ms(P + 'step_prep', 4.0, 4.5),
           ms(P + 'window_wait', 5.0, 6.5),
           ms('mxtpu.perf.fit_step', 7.0, 10.0),
           ms(P + 'step_prep', 7.0, 7.4),
           ms(P + 'window_wait', 7.5, 8.0)]
    op = '%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %a), kind=kLoop'
    busy = [(0.5, 3.6), (3.95, 5.2), (5.5, 8.5), (8.9, 11.0)]
    return Profile([
        Plane('/host:CPU', [Line('python3', fit)]),
        Plane('/device:TPU:0', [Line('XLA Ops', [ms(op, a, b)
                                                 for a, b in busy])])])


def test_unnamed_idle_time_on_a_hand_made_profile(tmp_path, monkeypatch,
                                                  capsys):
    folder = tmp_path / 'cell' / 'plugins' / 'profile' / 'x'
    folder.mkdir(parents=True)
    (folder / 'host.xplane.pb').write_bytes(b'')
    monkeypatch.setattr(harness, 'TRACE_DIR', str(tmp_path))
    monkeypatch.setattr(tr, 'load', lambda path: hand_made())
    fit_span_tree._tree_at.cache_clear()
    try:
        slice_ = {'steps': 20.0, 'trace': {}}
        assert read('fit_idle_unnamed_ms', slice_) == \
            pytest.approx((0.3 + 0.4) / 20)
        # its complement: the gap under the collector has a name
        assert read('fit_idle_host_ms', slice_) == \
            pytest.approx(0.35 / 20)
        tree = fit_span_tree.of_slice(slice_)
        assert [name for _, _, name in tree.named_gaps()] == [
            'mxtpu.perf.gc', tr.UNATTRIBUTED, tr.UNATTRIBUTED]
    finally:
        fit_span_tree._tree_at.cache_clear()
    out = capsys.readouterr().out
    assert ('[bench] idle gap on chip 0 that no span names: 0.300 ms at '
            '+4.200 ms (under it: perf.phase.window_wait 100%)') in out
    assert ('[bench] idle gap on chip 0 that no span names: 0.400 ms at '
            '+7.500 ms (under it: no span of the program)') in out
    assert 'that no span names: 0.350' not in out
