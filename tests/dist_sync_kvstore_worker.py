"""Worker script for the multi-process dist_sync kvstore test —
the analogue of the reference's ``tests/nightly/dist_sync_kvstore.py``
(exact arithmetic check of sync push/pull), launched by
``tools/launch.py --launcher local`` just like ``test_all.sh:37``.

Runs under JAX's CPU backend with jax.distributed (gloo transport).
"""
import os
import sys

os.environ['XLA_FLAGS'] = os.environ.get('XLA_FLAGS', '') + \
    ' --xla_force_host_platform_device_count=2'
import jax  # noqa: E402
jax.config.update('jax_platforms', 'cpu')

jax.distributed.initialize(
    coordinator_address=os.environ['MXTPU_COORDINATOR'],
    num_processes=int(os.environ['MXTPU_NUM_PROCESSES']),
    process_id=int(os.environ['MXTPU_PROCESS_ID']))

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))
import mxnet_tpu as mx  # noqa: E402

kv = mx.kv.create('dist_sync')
rank, nworker = kv.rank, kv.num_workers
assert nworker == int(os.environ['MXTPU_NUM_PROCESSES'])

shape = (3, 4)
big_shape = (50, 100)      # exercises the big-array path

kv.init(3, mx.nd.ones(shape))
kv.init(99, mx.nd.ones(big_shape))
kv.barrier()

# push rank-dependent values; sync semantics => pulled value aggregates
# every worker's push (kvstore_dist_server.h:179-197)
for it in range(3):
    kv.push(3, mx.nd.ones(shape) * (rank + 1))
    kv.push(99, mx.nd.ones(big_shape) * (rank + 1) * 2)
    kv.barrier()
    out = mx.nd.zeros(shape)
    kv.pull(3, out=out)
    expected = sum(r + 1 for r in range(nworker))
    got = out.asnumpy()
    assert np.allclose(got, expected), (it, got.ravel()[:4], expected)
    out_big = mx.nd.zeros(big_shape)
    kv.pull(99, out=out_big)
    expected_big = 2 * expected
    assert np.allclose(out_big.asnumpy(), expected_big)

kv.barrier()

# batched list push/pull: the whole key group crosses hosts as ONE
# fused all-reduce (DistKVStore.push -> allreduce_hosts_batch) — mixed
# shapes on purpose so the flatten/split layout is exercised
kv.init(7, mx.nd.zeros(shape))
kv.barrier()
kv.push([3, 99, 7],
        [[mx.nd.ones(shape) * (rank + 1)],
         [mx.nd.ones(big_shape) * (rank + 1) * 2],
         [mx.nd.ones(shape) * (rank + 1) * 3]])
kv.barrier()
outs = [mx.nd.zeros(shape), mx.nd.zeros(big_shape), mx.nd.zeros(shape)]
kv.pull([3, 99, 7], out=outs)
expected = sum(r + 1 for r in range(nworker))
for got, mult in zip(outs, (1, 2, 3)):
    assert np.allclose(got.asnumpy(), expected * mult), \
        (got.shape, got.asnumpy().ravel()[:4], expected * mult)

kv.barrier()

# big-key split: with the bound below big_shape's 5000 elements the
# same push call takes the fused path for the small keys AND the
# individual path for the big one (DistKVStore.push partitioning)
os.environ['MXNET_KVSTORE_BIGARRAY_BOUND'] = '4000'
kv.push([3, 99, 7],
        [[mx.nd.ones(shape) * (rank + 1)],
         [mx.nd.ones(big_shape) * (rank + 1) * 2],
         [mx.nd.ones(shape) * (rank + 1) * 3]])
kv.barrier()
outs = [mx.nd.zeros(shape), mx.nd.zeros(big_shape), mx.nd.zeros(shape)]
kv.pull([3, 99, 7], out=outs)
for got, mult in zip(outs, (1, 2, 3)):
    assert np.allclose(got.asnumpy(), expected * mult), \
        (got.shape, got.asnumpy().ravel()[:4], expected * mult)
del os.environ['MXNET_KVSTORE_BIGARRAY_BOUND']

# replicated-server optimizer: set_optimizer must install the updater
# LOCALLY (every rank applies the identical update to its replica) —
# a pull after push must return updated weights, not gradient sums
kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.5,
                                  rescale_grad=1.0, wd=0.0))
kv.init(11, mx.nd.ones(shape) * 10)
kv.barrier()
kv.push(11, mx.nd.ones(shape) * (rank + 1))
kv.barrier()
out11 = mx.nd.zeros(shape)
kv.pull(11, out=out11)
want = 10 - 0.5 * expected     # w - lr * sum_r(r+1)
assert np.allclose(out11.asnumpy(), want), (out11.asnumpy().ravel()[:4],
                                            want)

kv.barrier()
print('dist_sync_kvstore_worker rank %d OK' % rank)
