"""Peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports. A kind missing here is an error, never a
default. Copied from the program's ``roofline/hw.py`` row so that no
change to the program moves the yardstick.

TPU v5e (``"TPU v5 lite"``), per chip, from the Google Cloud "TPU v5e"
documentation: 197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GB of HBM.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
