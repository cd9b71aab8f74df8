"""Worker script for the 2-worker heartbeat-telemetry test
(tests/test_health.py): each rank marks a distinctive counter in its
instrument registry, the heartbeat piggyback ('mv2' protocol extension)
carries it to the rank-0 kv server, and rank 0 asserts the merged
cluster view contains BOTH ranks with their markers summed."""
import os
import sys
import time

os.environ['XLA_FLAGS'] = os.environ.get('XLA_FLAGS', '') + \
    ' --xla_force_host_platform_device_count=2'
import jax  # noqa: E402
jax.config.update('jax_platforms', 'cpu')

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))
import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import instrument  # noqa: E402

kv = mx.kv.create('dist_async')
rank, nworker = kv.rank, kv.num_workers
assert nworker == 2

instrument.inc('health.test_marker', 10 + rank)
instrument.set_gauge('health.test_gauge', float(rank))

kv.barrier()
time.sleep(2.5)                      # >= 2 heartbeat intervals
if rank == 0:
    view = kv.telemetry()
    got = sorted(view['ranks'])
    assert got == [0, 1], 'ranks in view: %r' % (got,)
    for r in (0, 1):
        c = view['ranks'][r]['counters'].get('health.test_marker')
        assert c == 10 + r, 'rank %d marker: %r' % (r, c)
        g = view['ranks'][r]['gauges'].get('health.test_gauge')
        assert g == float(r), 'rank %d gauge: %r' % (r, g)
    total = view['cluster']['counters'].get('health.test_marker')
    assert total == 21, 'cluster sum: %r' % (total,)
kv.barrier()
kv.close()
print('health_telemetry_worker rank %d OK' % rank, flush=True)
