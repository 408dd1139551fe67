"""Published peaks of the chips the benchmark may run on, keyed by the
exact ``device_kind`` jax reports. A kind that is not here is an error,
never a default: a utilization against a guessed peak is not a number.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 16 GB HBM2e at
819 GB/s, per chip. (Copied from bench.py ``_PEAK_TFLOPS``, which has
the FLOP/s only; the HBM figures are added here.)
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device kind {device_kind!r} is not in the benchmark's table "
            f"of peaks ({sorted(PEAKS)}); add it with its source, do not "
            "default it") from None
