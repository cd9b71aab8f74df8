"""Published peaks of the chips the benchmark knows, keyed by the
``device_kind`` JAX reports.  A kind that is not here is an error, never
a default: a utilization against a guessed peak is worse than none.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect per chip.  The same figures as ``mxnet_tpu.perfwatch.PEAKS``
(copied, so that a later PR that changes the program cannot move the
yardstick).
"""

PEAKS = {
    'TPU v5 lite': {'flops_bf16': 197e12, 'hbm_bytes_per_s': 819e9,
                    'hbm_bytes': 16e9, 'ici_bits_per_s': 1600e9},
}


class UnknownDevice(Exception):
    pass


def peaks_for(device_kind):
    for kind, row in PEAKS.items():
        if device_kind.startswith(kind):
            return row
    raise UnknownDevice('device_kind %r has no row in benchmark/peaks.py '
                        '(known: %s)' % (device_kind, sorted(PEAKS)))
