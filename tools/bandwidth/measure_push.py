#!/usr/bin/env python
"""dist_sync push-path diagnostic: per-key pushes vs ONE batched group
push (``DistKVStore.push`` -> ``allreduce_hosts_batch``).

The reference measured its push path with ``tools/bandwidth/measure.py``
(11.1 GB/s/GPU, README.md:30-40) and batched/sharded big arrays across
servers (``kvstore_dist.h:277-299``).  Here the equivalent batching is
one fused cross-host all-reduce for the whole key group; this worker
times both shapes of the same traffic.

Run under the launcher (CPU gloo transport works anywhere):

  python tools/launch.py -n 2 --launcher local \
      "python tools/bandwidth/measure_push.py"
"""
import os
import sys
import time

if 'MXTPU_COORDINATOR' in os.environ:
    os.environ['XLA_FLAGS'] = os.environ.get('XLA_FLAGS', '') + \
        ' --xla_force_host_platform_device_count=2'
    import jax
    jax.config.update('jax_platforms', 'cpu')
    jax.distributed.initialize(
        coordinator_address=os.environ['MXTPU_COORDINATOR'],
        num_processes=int(os.environ['MXTPU_NUM_PROCESSES']),
        process_id=int(os.environ['MXTPU_PROCESS_ID']))

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..', '..'))

import numpy as np                      # noqa: E402
import mxnet_tpu as mx                  # noqa: E402


def main(num_keys=160, total_mb=100.0, iters=3):
    kv = mx.kv.create('dist_sync')
    rank = kv.rank
    elems = int(total_mb * 1024 * 1024 / 4 / num_keys)
    keys = list(range(num_keys))
    vals = [mx.nd.ones((elems,)) * (rank + 1) for _ in keys]
    for k, v in zip(keys, vals):
        kv.init(k, v)
    kv.barrier()

    def sync_all():
        out = mx.nd.zeros((elems,))
        kv.pull(keys[-1], out=out)
        out.asnumpy()

    # per-key: one collective per parameter
    kv.barrier()
    t0 = time.time()
    for _ in range(iters):
        for k, v in zip(keys, vals):
            kv.push(k, v)
    sync_all()
    per_key = (time.time() - t0) / iters

    # batched: the whole group as one fused all-reduce
    kv.barrier()
    t0 = time.time()
    for _ in range(iters):
        kv.push(keys, [[v] for v in vals])
    sync_all()
    batched = (time.time() - t0) / iters

    if rank == 0:
        gb = total_mb / 1024
        print('push %d keys (%.0f MB total), %d workers:'
              % (num_keys, total_mb, kv.num_workers))
        print('  per-key : %.3fs  (%.2f GB/s)' % (per_key, gb / per_key))
        print('  batched : %.3fs  (%.2f GB/s)  %.1fx faster'
              % (batched, gb / batched, per_key / batched))
    kv.barrier()
    print('measure_push rank %d OK' % rank)


if __name__ == '__main__':
    main()
