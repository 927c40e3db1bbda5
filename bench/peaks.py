"""Peak rates of each chip the benchmark may run on, keyed by JAX's
``device_kind``.  A kind that is not here is an error, never a default.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16 per chip and 819 GB/s of HBM bandwidth.  The program
keeps float32 arrays, whose matrix products the chip runs as bf16 passes
at the default precision, so the bf16 peak is the one every FLOP count
here is divided by.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud, TPU v5e: 197 TFLOP/s bf16, "
                              "819 GB/s HBM"},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
