"""benchmark/trace_reduce.py on a recorded v5e trace and on hand-made
ones.

``recorded_small_fit.xplane.pb`` is three ``Module.fit`` steps of a
ResNet-20 (batch 32, 3x32x32, bf16) recorded on one TPU v5e with the
Python tracer off (my chip run, PR 23): the device plane's ``XLA
Modules`` line holds the step program three times at 547.5 to 548.0
microseconds, its ``XLA Ops`` line 2223 op events.
"""
import collections
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce as tr  # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        'recorded_small_fit.xplane.pb')

Event = collections.namedtuple('Event', 'name start_ns duration_ns')
Line = collections.namedtuple('Line', 'name events')
Plane = collections.namedtuple('Plane', 'name lines')
Profile = collections.namedtuple('Profile', 'planes')


@pytest.fixture(scope='module')
def recorded():
    return tr.load(RECORDED)


def test_interval_arithmetic():
    assert tr.union([(1, 3), (2, 5), (7, 8), (8, 8)]) == [(1, 5), (7, 8)]
    assert tr.total([(1, 5), (7, 8)]) == 5
    assert tr.subtract([(0, 10), (20, 30)], [(2, 3), (5, 25)]) == \
        [(0, 2), (3, 5), (25, 30)]
    assert tr.subtract([(0, 10)], []) == [(0, 10)]
    assert tr.gaps([(2, 3), (5, 25)], 0, 30) == [(0, 2), (3, 5), (25, 30)]


def test_op_text_is_parsed():
    conv = ('%fusion.12 = bf16[256,64,56,56]{1,0,3,2:T(8,128)(2,1)} '
            'fusion(bf16[256,64,56,56]{1,0,3,2} %a, bf16[64,64,1,1]{0,1,3,2} '
            '%b), kind=kOutput, calls=%fused_computation.12')
    loop = ('%multiply_reduce_fusion.7 = (bf16[256]{0:T(256)}, bf16[256]{0}) '
            'fusion(f32[256,64,56,56]{0,1,3,2} %x), kind=kLoop, '
            'calls=%fused_computation.7')
    start = ('%all-reduce-start.3 = f32[1024]{0} all-reduce-start(f32[1024]'
             '{0} %g), replica_groups={{0,1,2,3}}, to_apply=%add')
    assert tr.opcode(conv) == 'fusion' and tr.is_convolution(conv)
    assert tr.opcode(loop) == 'fusion' and not tr.is_convolution(loop)
    assert tr.opcode(start) == 'all-reduce-start' and tr.is_collective(start)
    assert not tr.is_collective(conv)
    assert tr.op_label(conv) == '%fusion.12 bf16[256,64,56,56] fusion'
    assert tr.op_label(loop) == '%multiply_reduce_fusion.7 bf16[256] fusion'
    assert tr.is_convolution('%c = f32[8,8]{1,0} convolution(f32[8,8] %a)')


def test_recorded_trace_busy_union_idle_share_and_step_time(recorded):
    planes = tr.device_planes(recorded)
    assert [p.name for p in planes] == ['/device:TPU:0']
    reduced = tr.reduce_profile(recorded, 'bench.slice')
    # no bench.slice span in this recording: the window is the ops' extent
    assert reduced['chips'] == 1 and reduced['op_events'] == 2223
    assert reduced['window_s'] == pytest.approx(8.928781e-3, rel=1e-6)
    assert reduced['busy_s'] == pytest.approx(1.61441e-3, rel=1e-5)
    idle_share = 1.0 - reduced['busy_s'] / reduced['window_s']
    assert idle_share == pytest.approx(0.8192, abs=1e-3)
    # three steps: the union of op intervals a step is within 3% of the
    # step program's own event on the XLA Modules line
    modules = [e.duration_ns for line in planes[0].lines
               if line.name == 'XLA Modules' for e in line.events
               if e.name.startswith('jit_step_m')]
    assert len(modules) == 3
    assert reduced['busy_s'] / 3 == pytest.approx(
        sum(modules) / 3 / 1e9, rel=0.03)
    assert reduced['collective_s'] == 0.0


def test_recorded_trace_top_ops_and_convolution_share(recorded):
    reduced = tr.reduce_profile(recorded, 'bench.slice')
    ops = reduced['device_ops']
    assert len(ops) == 10
    assert ops[0][0] == '%multiply_reduce_fusion.5 bf16[16] fusion'
    assert ops[0][1] == pytest.approx(4.8439e-5, rel=1e-4)
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    assert reduced['conv_s'] / reduced['busy_s'] == pytest.approx(0.8438,
                                                                  abs=1e-3)


def test_recorded_trace_window_from_a_host_span(recorded):
    names = collections.Counter(n for _, _, n in tr.host_spans(recorded))
    assert names == {'bench.iter_next': 3, 'bench.batch_end': 2}
    lo, hi = tr.window_of(recorded, 'bench.batch_end')
    assert (lo, hi) == (53446806.0, 57078215.0)
    inside = tr.reduce_profile(recorded, 'bench.batch_end')
    assert inside['window_s'] == pytest.approx((hi - lo) / 1e9)
    # exactly one of the three steps lies between the two callbacks
    assert inside['busy_s'] == pytest.approx(1.61441e-3 / 3, rel=0.05)
    # the recording's own spans are microseconds long: no gap is theirs
    assert {name for name, _ in inside['idle_gaps']} == {tr.UNATTRIBUTED}
    assert len(inside['idle_gaps']) == 5


def test_gap_attribution():
    idle = [(0, 100), (200, 1200), (2000, 2400)]
    spans = [(150, 1100, 'bench.iter_next'),       # covers 900 of 1000
             (2000, 2100, 'bench.batch_end')]      # covers 100 of 400
    assert tr.attribute_gaps(idle, spans, most=2) == [
        ['bench.iter_next', 1e-6], [tr.UNATTRIBUTED, 4e-7]]


def hand_made(device_lines, host_events=()):
    return Profile([
        Plane('/host:CPU', [Line('main', [Event(*e) for e in host_events])]),
    ] + [Plane('/device:TPU:%d' % i,
               [Line(name, [Event(*e) for e in events])
                for name, events in lines.items()])
         for i, lines in enumerate(device_lines)])


def test_exposed_collective_time_on_a_hand_made_overlap():
    conv = '%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %a), kind=kOutput, calls=%f'
    loop = '%fusion.2 = bf16[8]{0} fusion(bf16[8]{0} %a), kind=kLoop, calls=%g'
    start = '%all-reduce-start.1 = f32[8]{0} all-reduce-start(f32[8]{0} %g)'
    done = '%all-reduce-done.1 = f32[8]{0} all-reduce-done(f32[8]{0} %s)'
    sync = '%all-gather.4 = f32[8]{0} all-gather(f32[2]{0} %p)'
    copy = '%copy-start.9 = (f32[8]{0}, f32[8]{0}) copy-start(f32[8]{0} %w)'
    chip0 = {
        'XLA Ops': [(conv, 0, 100), (start, 100, 2), (loop, 110, 50),
                    (done, 200, 10), (sync, 300, 40), (conv, 400, 100)],
        # the asynchronous all-reduce runs from 100 to 210: hidden behind
        # the loop fusion from 110 to 160, exposed for the other 60
        'Async XLA Ops': [(start, 100, 110), (copy, 0, 500)],
    }
    chip1 = {'XLA Ops': [(conv, 0, 500)], 'Async XLA Ops': []}
    profile = hand_made([chip0, chip1],
                        [('bench.slice', 0, 1000), ('bench.iter_next', 500, 500)])
    reduced = tr.reduce_profile(profile, 'bench.slice')
    assert reduced['chips'] == 2 and reduced['window_s'] == 1e-6
    busy0 = 100 + 2 + 50 + 10 + 40 + 100
    assert reduced['busy_s'] == pytest.approx((busy0 + 500) / 2 / 1e9)
    assert reduced['conv_s'] == pytest.approx((200 + 500) / 2 / 1e9)
    # collectives: the span 100..210 and the synchronous gather 300..340
    assert reduced['collective_s'] == pytest.approx((110 + 40) / 2 / 1e9)
    assert reduced['collective_exposed_s'] == pytest.approx(
        (60 + 40) / 2 / 1e9)
    assert reduced['idle_gaps'][0] == ['bench.iter_next', 5e-7]
    # fewer chips asked for: only the first plane is read
    assert tr.reduce_profile(profile, 'bench.slice', chips=1)['busy_s'] == \
        pytest.approx(busy0 / 1e9)


def test_a_trace_with_no_device_plane_reduces_to_nothing():
    assert tr.reduce_profile(hand_made([]), 'bench.slice') is None
