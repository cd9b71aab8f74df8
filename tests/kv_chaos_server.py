"""Chaos-test helper: run an AsyncKVServer in its own process so the
resilience tests can ``kill -9`` it mid-training and restart it from its
backing file (tests/test_resilience.py, tools/check_resilience.py).

argv: PORT BACKING_PATH [NUM_WORKERS]
Prints ``READY <port>`` once listening, then parks forever.
"""
import os
import sys
import time

os.environ['XLA_FLAGS'] = os.environ.get('XLA_FLAGS', '') + \
    ' --xla_force_host_platform_device_count=2'
import jax  # noqa: E402
jax.config.update('jax_platforms', 'cpu')

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))
from mxnet_tpu.kvstore_server import AsyncKVServer  # noqa: E402

port = int(sys.argv[1])
backing = sys.argv[2]
nworkers = int(sys.argv[3]) if len(sys.argv) > 3 else 1

srv = AsyncKVServer(port=port, num_workers=nworkers, backing=backing,
                    sync_every=1)
print('READY %d' % srv.port, flush=True)
while True:
    time.sleep(0.1)
