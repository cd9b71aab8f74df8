"""Worker script for the SIGTERM flight-recorder test
(tests/test_health.py): runs a long Module.fit with the flight recorder
and sentinels installed; the parent waits for the first write-ahead
snapshot, then SIGTERMs the process mid-fit and validates the dump the
signal hook left behind."""
import os
import sys

os.environ['JAX_PLATFORMS'] = 'cpu'
os.environ.setdefault('MXTPU_FLIGHT_RECORDER_EVERY', '2')
os.environ['MXTPU_HEALTH_SENTINELS'] = '1'
# MXTPU_FLIGHT_RECORDER comes from the parent's environment

import jax  # noqa: E402
jax.config.update('jax_platforms', 'cpu')

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))
import mxnet_tpu as mx  # noqa: E402

rng = np.random.RandomState(0)
bs, d, classes = 16, 10, 4
X = rng.randn(8 * bs, d).astype(np.float32)
Y = (X @ rng.randn(d, classes)).argmax(1).astype(np.float32)
net = mx.sym.SoftmaxOutput(
    mx.sym.FullyConnected(mx.sym.Variable('data'), num_hidden=classes,
                          name='fc'), name='softmax')
it = mx.io.NDArrayIter(data=X, label=Y, batch_size=bs)
mod = mx.mod.Module(net)
print('READY', flush=True)
# enough epochs to outlive the parent's SIGTERM by a wide margin
mod.fit(it, num_epoch=100000, optimizer='sgd',
        optimizer_params={'learning_rate': 0.01},
        eval_metric='acc', initializer=mx.init.Uniform(0.05),
        batch_end_callback=mx.callback.Speedometer(bs, 2))
raise AssertionError('fit finished before SIGTERM arrived')
