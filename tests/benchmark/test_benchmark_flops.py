"""benchmark/flops.py against hand counts, each configuration's pinned
model against what the program builds, benchmark/peaks.py against an
unknown device, and the data-defined metric arithmetic."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flops, harness, manifest, peaks  # noqa: E402

SPEC = manifest.load_manifest()


def test_one_bottleneck_block_by_hand():
    import mxnet_tpu as mx
    from mxnet_tpu.models import resnet
    unit = resnet.residual_unit(mx.sym.Variable('data'), 256, (1, 1), False,
                                name='u', bottle_neck=True)
    macs, rows = flops.forward_macs(unit, {'data': (1, 64, 8, 8)})
    by_hand = {'u_conv1': 8 * 8 * 64 * 64,           # 1x1, 64 -> 64
               'u_conv2': 8 * 8 * 64 * 64 * 9,       # 3x3, 64 -> 64
               'u_conv3': 8 * 8 * 64 * 256,          # 1x1, 64 -> 256
               'u_sc': 8 * 8 * 64 * 256}             # 1x1 shortcut
    assert {name: n for name, _, n, _ in rows} == by_hand
    assert rows[1][3] == (64, 64, 3, 3)
    assert macs == 4718592
    assert flops.train_step_flops(unit, {'data': (1, 64, 8, 8)}) == 6 * macs


def test_resnet50_forward_is_4_1_g_multiply_adds():
    from mxnet_tpu import models
    symbol = models.get_symbol('resnet-50', num_classes=1000,
                               image_shape=(3, 224, 224))
    macs, rows = flops.forward_macs(symbol, {'data': (2, 3, 224, 224)})
    assert abs(macs / 2 - 4.1e9) / 4.1e9 < 0.05
    assert len(rows) == 54                      # 53 convolutions and the FC


# multiply-adds of one forward pass and learnable numbers, as published:
# ResNet-50 4.1 G and 25.6 M (He et al.), Inception-v3 5.7 G and 23.8 M
# (Szegedy et al. 2015, table 3 and the released model)
PUBLISHED = {'resnet50': (4.1e9, 25.6e6), 'inception_v3': (5.7e9, 23.8e6)}
# the configurations of the cells that the ``fit`` driver runs: this file is
# that driver's (an ``image_shape``, ``flops.pinned``); another driver's
# configurations are pinned in its own test file
FIT_CONFIGS = sorted({cell['config'] for cell in SPEC['workloads']
                      if manifest.load_cell(cell['name'])['driver'] == 'fit'})


def test_every_fit_configuration_has_its_published_counts_here():
    assert FIT_CONFIGS and set(FIT_CONFIGS) == set(PUBLISHED)


@pytest.mark.parametrize('name', FIT_CONFIGS)
def test_configuration_pins_the_published_model_and_the_program_builds_it(
        name):
    entry = manifest.config_entry(SPEC, name)
    config = manifest.load_config(SPEC, entry['name'])
    pinned = config['pinned']
    macs, parameters = PUBLISHED[entry['name']]
    assert abs(pinned['forward_macs_per_sample'] - macs) / macs < 0.05
    assert abs(pinned['parameters'] - parameters) / parameters < 0.01
    symbol = harness.build_symbol(config)
    assert flops.pinned(symbol, config['image_shape']) == \
        {k: pinned[k] for k in ('forward_macs_per_sample', 'parameters',
                                'weights')}
    harness.check_pinned(symbol, config, rehearsal=False)


def test_a_narrower_or_shallower_model_under_the_same_name_is_refused():
    from mxnet_tpu import models
    config = manifest.load_config(SPEC, 'resnet50')
    shallower = models.get_symbol('resnet-34', num_classes=1000,
                                  image_shape=(3, 224, 224))
    with pytest.raises(harness.BenchmarkError, match='builds another model'):
        harness.check_pinned(shallower, config, rehearsal=False)
    narrower = dict(config, pinned=dict(config['pinned'], weights=[
        [name, [shape[0] // 2] + shape[1:]] if name == 'stage3_unit2_conv2'
        else [name, shape] for name, shape in config['pinned']['weights']]))
    with pytest.raises(harness.BenchmarkError,
                       match=r"pinned \['stage3_unit2_conv2', \[128, 256, 3, "
                       r"3\]\], built \['stage3_unit2_conv2', \[256, 256"):
        harness.check_pinned(harness.build_symbol(config), narrower, False)
    # only a rehearsal, at other sizes, runs a configuration with no pin
    unpinned = {k: v for k, v in config.items() if k != 'pinned'}
    harness.check_pinned(shallower, unpinned, rehearsal=True)
    with pytest.raises(harness.BenchmarkError, match='pins no model'):
        harness.check_pinned(shallower, unpinned, rehearsal=False)


def test_stride_and_fully_connected_counts():
    import mxnet_tpu as mx
    data = mx.sym.Variable('data')
    conv = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3),
                              stride=(2, 2), pad=(1, 1), name='c')
    net = mx.sym.FullyConnected(mx.sym.Flatten(conv), num_hidden=5,
                                name='f')
    macs, rows = flops.forward_macs(net, {'data': (3, 4, 8, 8)})
    assert dict((n, m) for n, _, m, _ in rows) == {
        'c': 3 * 8 * 4 * 4 * (4 * 3 * 3), 'f': 3 * 5 * (8 * 4 * 4)}


def test_unknown_device_kind_is_an_error_not_a_default():
    assert peaks.peaks_for('TPU v5 lite')['flops_bf16'] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for('cpu')


def test_data_defined_metric_arithmetic():
    slice_ = {
        'snap0': {'counters': {'c': 3}, 'histograms': {
            'h': {'sum': 1.0, 'count': 10}}},
        'snap1': {'counters': {'c': 13}, 'histograms': {
            'h': {'sum': 1.5, 'count': 20}, 'g': {'sum': 0.25, 'count': 5}}},
        'steps': 20.0, 'rows': 30.0}
    mean = {'read': {'numerator': ['histogram_sum:h', 'histogram_sum:g'],
                     'denominator': ['slice:steps'], 'scale': 1000.0}}
    assert harness.evaluate(mean, slice_) == pytest.approx(37.5)
    ratio = {'read': {'numerator': ['counter:c'],
                      'denominator': ['counter:c', 'slice:rows'],
                      'scale': 100.0}}
    assert harness.evaluate(ratio, slice_) == pytest.approx(25.0)
    per_count = {'read': {'numerator': ['histogram_sum:h'],
                          'denominator': ['histogram_count:h']}}
    assert harness.evaluate(per_count, slice_) == pytest.approx(0.05)
    # a series the program never wrote: nothing to read, no metric
    absent = {'read': {'numerator': ['histogram_sum:nope'],
                       'denominator': ['slice:steps']}}
    assert harness.evaluate(absent, slice_) is None
    assert harness.evaluate(
        {'read': {'reader': 'fit_step_device_ms'}}, slice_) is None


def test_trace_readers_on_a_made_up_slice():
    slice_ = {'steps': 10.0, 'chips': 2.0, 'step_flops': 197e12 * 0.01,
              'device_kind': 'TPU v5 lite',
              'trace': {'busy_s': 0.5, 'conv_s': 0.2, 'chips': 2,
                        'collective_s': 0.05, 'collective_exposed_s': 0.01}}

    def read(name):
        return harness.evaluate({'read': {'reader': name}}, slice_)
    assert read('fit_step_device_ms') == pytest.approx(50.0)
    assert read('fit_conv_share_pct') == pytest.approx(40.0)
    # 1% of a chip-second of FLOPs in 50 ms on 2 chips: 10%
    assert read('fit_step_flops_pct') == pytest.approx(10.0)
    assert read('fit_collective_ms') == pytest.approx(5.0)
    assert read('fit_collective_exposed_ms') == pytest.approx(1.0)
    slice_['trace']['chips'] = 1
    assert read('fit_collective_ms') is None
