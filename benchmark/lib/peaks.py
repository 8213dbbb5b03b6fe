"""The chip's published peaks, keyed by ``device_kind`` as JAX reports
it.  The benchmark's own copy: a later PR may change the program's table
(``mxnet_tpu.telemetry.DEVICE_PEAKS``), not the yardstick.  A device that
is not here is an error, never a default."""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
                  '16 GB HBM2e at 819 GB/s per chip',
    },
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit("device kind %r is not in benchmark/lib/peaks.py; "
                         "add it with its source before measuring on it"
                         % (device_kind,))
