"""Test harness config: force the XLA CPU backend with 8 virtual devices so
multi-device (mesh/sharding/kvstore) code paths run without TPU hardware —
the stand-in for the reference's fake-multi-GPU kvstore tests
(tests/python/unittest/test_kvstore.py) and local-cluster forks
(tests/nightly/dist_sync_kvstore.py).

The perfwatch/commwatch peak tables hold real chips only, so the virtual
CPU devices get nominal MXTPU_PEAK_FLOPS / MXTPU_PEAK_BW figures: the
MFU and comm-fraction gauges stay defined (not meaningful) in tests and
in the child processes they start.
"""
import os

flags = os.environ.get('XLA_FLAGS', '')
if 'xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = \
        flags + ' --xla_force_host_platform_device_count=8'

import jax  # noqa: E402

jax.config.update('jax_platforms', 'cpu')

os.environ.setdefault('MXTPU_PEAK_FLOPS', '2e11')
os.environ.setdefault('MXTPU_PEAK_BW', '1e10')
