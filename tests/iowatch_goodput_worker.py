"""Worker script for the 2-worker per-rank goodput merge test
(tests/test_iowatch.py): each rank opens a real goodput ledger under
MXTPU_IOWATCH, rank 1 deliberately burns most of its wall clock in the
input_stall bucket, the ledger's published ``goodput.*`` gauges ride
the heartbeat piggyback, and rank 0 asserts the kv server's merged
cluster view carries BOTH ranks' fractions, the ``cluster.goodput``
gauge equal to the BINDING (minimum) rank's fraction, and the worst-fed
attribution naming rank 1."""
import os
import sys
import time

os.environ['XLA_FLAGS'] = os.environ.get('XLA_FLAGS', '') + \
    ' --xla_force_host_platform_device_count=2'
import jax  # noqa: E402
jax.config.update('jax_platforms', 'cpu')

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))
import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import iowatch  # noqa: E402

kv = mx.kv.create('dist_async')
rank, nworker = kv.rank, kv.num_workers
assert nworker == 2
assert iowatch.enabled(), 'MXTPU_IOWATCH did not arm'

ledger = iowatch.goodput_begin()
time.sleep(0.3)
if rank == 1:
    # charge ~all of the elapsed wall to input_stall: rank 1 must come
    # out the binding (worst-fed) rank by a wide, assertable margin
    ledger.charge('input_stall', 0.29)
snap = iowatch.goodput_end()
assert snap['fraction'] > 0.0 or rank == 1

kv.barrier()
time.sleep(2.5)                      # >= 2 heartbeat intervals
if rank == 0:
    view = kv.telemetry()
    fracs = {r: view['ranks'][r]['gauges'].get('goodput.fraction')
             for r in (0, 1)}
    assert all(isinstance(f, float) for f in fracs.values()), \
        'per-rank goodput gauges missing: %r' % (fracs,)
    assert fracs[0] > fracs[1], 'rank 1 should be worst-fed: %r' % fracs
    cg = view['cluster']['gauges'].get('cluster.goodput')
    assert cg == min(fracs.values()), \
        'cluster.goodput %r != binding rank fraction %r' \
        % (cg, min(fracs.values()))
    worst = view['cluster'].get('goodput')
    assert worst and int(worst['rank']) == 1, \
        'worst-fed attribution: %r' % (worst,)
kv.barrier()
kv.close()
print('iowatch_goodput_worker rank %d OK' % rank, flush=True)
