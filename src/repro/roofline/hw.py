"""Hardware peaks for the roofline / timing engine, keyed by the
``device_kind`` JAX reports.

TPU v5e (``"TPU v5 lite"``), per chip, from the Google Cloud "TPU v5e"
documentation: 197 TFLOP/s bf16, 819 GB/s HBM (16 GB), 1,600 Gbit/s of
inter-chip interconnect (4 links of ~50 GB/s).
"""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class Hardware:
    name: str
    peak_flops_bf16: float       # per chip, FLOP/s
    hbm_bw: float                # per chip, B/s
    ici_link_bw: float           # per link per direction, B/s
    ici_links: int               # links per chip (2D torus: 4)
    hbm_bytes: float             # capacity per chip
    vmem_bytes: float            # VMEM per core


HW_V5E = Hardware(
    name="tpu_v5e",
    peak_flops_bf16=197e12,
    hbm_bw=819e9,
    ici_link_bw=50e9,
    ici_links=4,
    hbm_bytes=16e9,
    vmem_bytes=128 * 2**20,
)

#: device_kind -> peaks. A TPU kind missing here is an error, never a
#: silent default to another chip's numbers.
PEAKS = {"TPU v5 lite": HW_V5E}


def peaks(device_kind: str) -> Hardware:
    """Peaks of the chip JAX reports as ``device_kind``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak table for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def device_peaks(device) -> Optional[Hardware]:
    """Peaks of a ``jax.Device``: ``None`` off the TPU (a CPU run has no
    device roofline share), the table row for a known TPU, and an error
    for a TPU kind the table does not hold."""
    if device.platform != "tpu":
        return None
    return peaks(device.device_kind)
