"""Worker script for the multi-process dist_async kvstore test — the
analogue of the reference's async local-cluster run
(``tests/nightly/dist_sync_kvstore.py`` with ``kv_type='dist_async'``):
workers push independently, the rank-0-hosted server applies every push
on arrival, pulls converge to the total once all pushes landed.

No jax.distributed needed: the async transport IS the TCP server.
"""
import os
import sys
import time

os.environ['XLA_FLAGS'] = os.environ.get('XLA_FLAGS', '') + \
    ' --xla_force_host_platform_device_count=2'
import jax  # noqa: E402
jax.config.update('jax_platforms', 'cpu')

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))
import mxnet_tpu as mx  # noqa: E402

kv = mx.kv.create('dist_async')
rank, nworker = kv.rank, kv.num_workers
assert nworker == int(os.environ['MXTPU_NUM_PROCESSES'])
assert kv.type == 'dist_async'

shape = (3, 4)
kv.init(7, mx.nd.zeros(shape))

# no optimizer set: pushes overwrite-on-arrival; with the Test optimizer
# below, pushes accumulate on arrival — exercise the updater path.
kv.set_optimizer(mx.optimizer.Test(rescale_grad=1.0))

ITERS = 5
t0 = time.time()
for it in range(ITERS):
    # non-blocking: all pushes of this loop return before the server
    # necessarily applied them
    kv.push(7, mx.nd.ones(shape))
push_time = time.time() - t0

kv.barrier()           # drains this worker's queue (same socket) first?
# barrier rides the same socket AFTER the pushes, so this worker's
# pushes are all applied once the barrier completes on the server; the
# barrier releases only when every worker reached it -> all applied.
out = mx.nd.zeros(shape)
kv.pull(7, out=out)
expected = ITERS * nworker      # Test optimizer: weight += grad
got = out.asnumpy()
assert np.allclose(got, expected), (got.ravel()[:4], expected)

kv.barrier()
kv.close()
print('dist_async_kvstore_worker rank %d OK (push %.4fs)'
      % (rank, push_time))
