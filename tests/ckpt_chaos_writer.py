"""Chaos-test helper: write checkpoints in a tight loop so the parent
test can ``kill -9`` this process at a random instant and assert that
``find_latest_checkpoint`` still points at a loadable file (the atomic
tmp+fsync+rename commit in model.save_checkpoint).

argv: PREFIX [N_EPOCHS]
Prints ``EPOCH <n>`` after each commit.
"""
import os
import sys

os.environ['XLA_FLAGS'] = os.environ.get('XLA_FLAGS', '') + \
    ' --xla_force_host_platform_device_count=2'
import jax  # noqa: E402
jax.config.update('jax_platforms', 'cpu')

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))
import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import nd  # noqa: E402
from mxnet_tpu.model import save_checkpoint  # noqa: E402

prefix = sys.argv[1]
n_epochs = int(sys.argv[2]) if len(sys.argv) > 2 else 200

# big enough that a kill lands mid-write with decent probability
arg_params = {'w%d' % i: nd.array(np.full((256, 256), float(i),
                                          np.float32))
              for i in range(4)}

print('START', flush=True)
for epoch in range(1, n_epochs + 1):
    save_checkpoint(prefix, epoch, None, arg_params, {})
    print('EPOCH %d' % epoch, flush=True)
