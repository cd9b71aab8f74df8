"""Worker for the multi-process END-TO-END training convergence test —
the analogue of the reference's ``tests/nightly/dist_lenet.py`` (train a
real conv net across forked workers through the dist kvstore, driven by
``tools/launch.py`` exactly like ``tests/nightly/test_all.sh:65-73``).

Each worker holds a deterministic shard of a synthetic-teacher dataset;
``Module.fit(kvstore=$MXTPU_CONV_MODE)`` aggregates gradients through
dist_sync/dist_async.  Rank 0 saves the final params so the harness can
check sync training is (float-)identical to a single-process run over
the same global batches.
"""
import os
import sys

os.environ['XLA_FLAGS'] = os.environ.get('XLA_FLAGS', '') + \
    ' --xla_force_host_platform_device_count=2'
import jax  # noqa: E402
jax.config.update('jax_platforms', 'cpu')

mode = os.environ.get('MXTPU_CONV_MODE', 'dist_sync')
# dist_sync aggregates through jax.distributed collectives; dist_async
# rides the host TCP parameter server and reads rank/size straight
# from the launcher env (kvstore.py:265) — initializing jax.distributed
# for async would only add the coordinator's topology exchange (a
# known in-suite flake source) without using it.
if mode == 'dist_sync':
    jax.distributed.initialize(
        coordinator_address=os.environ['MXTPU_COORDINATOR'],
        num_processes=int(os.environ['MXTPU_NUM_PROCESSES']),
        process_id=int(os.environ['MXTPU_PROCESS_ID']))

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))
import mxnet_tpu as mx  # noqa: E402
from test_dist_convergence import (make_dataset, build_lenet,  # noqa: E402
                                   GLOBAL_BS, EPOCHS, LR, SEED)
nworker = int(os.environ['MXTPU_NUM_PROCESSES'])
rank = int(os.environ['MXTPU_PROCESS_ID'])

X, Y = make_dataset()
local_bs = GLOBAL_BS // nworker
steps = X.shape[0] // GLOBAL_BS
# shard: global step s = concat over ranks of
#   X[s*G + r*local : s*G + (r+1)*local] — so the union of worker
# batches at each step IS the single-process global batch
idx = np.concatenate([
    np.arange(s * GLOBAL_BS + rank * local_bs,
              s * GLOBAL_BS + (rank + 1) * local_bs)
    for s in range(steps)])
it = mx.io.NDArrayIter(data=X[idx], label=Y[idx], batch_size=local_bs)

mx.random.seed(SEED)
mod = mx.mod.Module(build_lenet(), context=mx.cpu())
metric = mx.metric.create('acc')
# momentum under async training multiplies the effective step by the
# number of concurrent pushers (1/(1-mu) per pusher) — dist_async runs
# momentum-free, the standard async-SGD configuration
momentum = 0.9 if mode == 'dist_sync' else 0.0
mod.fit(it, num_epoch=EPOCHS, kvstore=mode, optimizer='sgd',
        optimizer_params={'learning_rate': LR, 'momentum': momentum,
                          'wd': 0.0},
        initializer=mx.init.Xavier(rnd_type='uniform',
                                   factor_type='avg', magnitude=2.0),
        eval_metric=metric)

# final training accuracy on this worker's shard
metric.reset()
mod.score(mx.io.NDArrayIter(data=X[idx], label=Y[idx],
                            batch_size=local_bs), metric)
name, acc = metric.get()
print('rank %d final acc %.4f' % (rank, acc), flush=True)
min_acc = float(os.environ.get('MXTPU_CONV_MIN_ACC', 0.85))
assert acc > min_acc, 'rank %d accuracy %.4f below threshold' % (rank,
                                                                 acc)

if rank == 0 and os.environ.get('MXTPU_CONV_OUT'):
    arg_params, aux_params = mod.get_params()
    mx.nd.save(os.environ['MXTPU_CONV_OUT'],
               {('arg:%s' % k): v for k, v in arg_params.items()})

# cross-rank agreement under sync training is implied: every rank
# pulls the same server values each step, and the harness separately
# checks rank 0's params against the single-process oracle.
print('dist_convergence_worker rank %d OK' % rank, flush=True)
