"""The one table of chip peaks the benchmark divides by.

Source: Google Cloud documentation, "TPU v5e" (system architecture): 197
TFLOP/s bf16, 819 GB/s of HBM bandwidth and 16 GB of HBM per chip. Keyed by
``jax.Device.device_kind``; a kind that is not here is an error, never a
default, and no environment variable overrides a number.
"""

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks_for(device_kind):
    """Peak FLOP/s, HBM bytes/s and HBM bytes of one chip of this kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError("no peaks known for device kind %r (chipbench/peaks.py "
                       "lists %s)" % (device_kind, sorted(PEAKS))) from None
