"""Auxiliary subsystem tests: visualization, callbacks, monitor,
profiler, engine mode, image utils, torch bridge, bandwidth tool."""
import json
import logging
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym


def _mlp():
    data = sym.Variable('data')
    fc1 = sym.FullyConnected(data, num_hidden=8, name='fc1')
    act = sym.Activation(fc1, act_type='relu', name='relu1')
    fc2 = sym.FullyConnected(act, num_hidden=4, name='fc2')
    return sym.SoftmaxOutput(fc2, name='softmax')


def test_print_summary(capsys):
    mx.viz.print_summary(_mlp(), shape={'data': (4, 16)})
    out = capsys.readouterr().out
    assert 'fc1' in out and 'Total params' in out


def test_speedometer_runs():
    from mxnet_tpu.callback import Speedometer
    from mxnet_tpu.module.base_module import BatchEndParam
    import mxnet_tpu.metric as metric
    s = Speedometer(32, frequent=1)
    m = metric.create('acc')
    for i in range(3):
        s(BatchEndParam(epoch=0, nbatch=i, eval_metric=m, locals={}))


def test_monitor_taps():
    mon = mx.monitor.Monitor(interval=1, pattern='.*fc.*')
    ex = _mlp().simple_bind(mx.cpu(), data=(2, 16))
    mon.install(ex)
    mon.tic()
    ex.forward(is_train=True)
    res = mon.toc()
    assert any('fc1' in name for _, name, _ in res)


def test_profiler_chrome_trace(tmp_path):
    from mxnet_tpu import instrument, profiler
    f = str(tmp_path / 'prof.json')
    profiler.profiler_set_config(filename=f)
    # the dump holds every span of the process: drop what test files that
    # ran earlier in this worker left behind (a serving test's requests)
    instrument.clear_trace()
    with profiler.Scope('step'):
        nd.dot(nd.ones((64, 64)), nd.ones((64, 64))).wait_to_read()
    profiler.dump_profile()
    data = json.load(open(f))
    assert data['traceEvents'][0]['name'] == 'step'


def test_naive_engine_mode():
    import jax
    from mxnet_tpu import engine
    engine.set_engine_type('NaiveEngine')
    try:
        assert jax.config.jax_disable_jit
        a = nd.relu(nd.array([-1.0, 1.0]))
        assert np.allclose(a.asnumpy(), [0, 1])
    finally:
        engine.set_engine_type('ThreadedEnginePerDevice')
    assert not jax.config.jax_disable_jit


def test_image_utils():
    from mxnet_tpu import image, recordio
    yy, xx = np.mgrid[0:40, 0:30]
    img = np.stack([yy * 6, xx * 8, (yy + xx) * 3], -1).astype(np.uint8)
    s = recordio.pack_img(recordio.IRHeader(0, 0.0, 0, 0), img)
    _, blob = recordio.unpack(s)
    decoded = image.imdecode(blob)
    assert decoded.shape == (40, 30, 3)
    short = image.resize_short(decoded, 20)
    assert min(short.shape[:2]) == 20
    crop, _ = image.center_crop(decoded, (16, 16))
    assert crop.shape == (16, 16, 3)
    normed = image.color_normalize(crop, mean=(1.0, 2.0, 3.0))
    assert normed.dtype == np.float32


def test_image_iter(tmp_path):
    from mxnet_tpu import image, recordio
    frec = str(tmp_path / 'd.rec')
    w = recordio.MXRecordIO(frec, 'w')
    rng = np.random.RandomState(0)
    for i in range(8):
        img = (rng.rand(40, 40, 3) * 255).astype(np.uint8)
        w.write(recordio.pack_img(recordio.IRHeader(0, float(i % 2), i, 0),
                                  img))
    del w
    it = image.ImageIter(4, (3, 32, 32), path_imgrec=frec,
                         rand_mirror=True, mean=True, std=True)
    batch = next(iter(it))
    assert batch.data[0].shape == (4, 3, 32, 32)


def test_torch_bridge():
    torch = pytest.importorskip('torch')
    from mxnet_tpu import torch_bridge as th
    a = nd.array([[1.0, -2.0], [3.0, 4.0]])
    out = th.th_call('abs', a)
    assert np.allclose(out.asnumpy(), np.abs(a.asnumpy()))

    lin = torch.nn.Linear(4, 2)
    mod = th.TorchModule(lin)
    x = nd.array(np.random.rand(3, 4).astype(np.float32))
    y = mod.forward(x, requires_grad=True)
    assert y.shape == (3, 2)
    gx = mod.backward(nd.ones((3, 2)))
    assert gx[0].shape == (3, 4)

    crit = th.TorchCriterion(torch.nn.MSELoss())
    loss = crit.forward(nd.ones((2, 2)), nd.zeros((2, 2)))
    assert abs(loss - 1.0) < 1e-6
    g = crit.backward()
    assert g.shape == (2, 2)


def test_bandwidth_tool():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        'measure', os.path.join(os.path.dirname(__file__), '..', 'tools',
                                'bandwidth', 'measure.py'))
    measure = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(measure)
    bw = measure.measure(num_devices=4, size_mb=1, iters=2)
    assert bw > 0


def test_plot_network_graphviz_optional():
    try:
        import graphviz  # noqa
    except ImportError:
        pytest.skip('graphviz not installed')
    dot = mx.viz.plot_network(_mlp(), shape={'data': (4, 16)})
    assert dot is not None


def test_find_latest_checkpoint(tmp_path):
    """Auto-resume discovery (recovery story: resume from the newest
    prefix-NNNN.params)."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    import numpy as np
    prefix = str(tmp_path / 'run1')
    assert mx.model.find_latest_checkpoint(prefix) is None
    for e in (1, 2, 7):
        nd.save('%s-%04d.params' % (prefix, e),
                {'arg:w': nd.array(np.zeros(2, np.float32))})
    assert mx.model.find_latest_checkpoint(prefix) == 7
    # a sibling prefix does not leak in
    nd.save(str(tmp_path / 'run2-0009.params'),
            {'arg:w': nd.array(np.zeros(2, np.float32))})
    assert mx.model.find_latest_checkpoint(prefix) == 7


def test_package_import_initializes_no_backend():
    """`import mxnet_tpu` must NOT initialize a JAX backend: building a
    PRNGKey (or anything device-touching) at import would claim the
    accelerator before the caller could pin a platform (round-5
    regression: the module-scope _RandomState eagerly built its key)."""
    import subprocess
    import sys
    code = (
        "import mxnet_tpu\n"
        "import jax._src.xla_bridge as xb\n"
        "assert not xb._backends, list(xb._backends)\n"
        "print('LAZY-IMPORT-OK')\n")
    proc = subprocess.run([sys.executable, '-c', code],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0 and 'LAZY-IMPORT-OK' in proc.stdout, \
        (proc.stdout[-500:], proc.stderr[-500:])
