"""Published per-chip peaks, keyed by ``device_kind`` as JAX reports it.

Copied from the published table so that a change to the program cannot
move the yardstick.  A device kind missing from the table is an error.

The table has no float32 vector-unit (VPU) peak: Google publishes only
the matrix unit's bf16 rate for v5e.  A VPU-bound float32 kernel's
compute bound is then far below the time it can take, and its roofline
share is set by the bytes term.
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops: float        # peak bf16 FLOP/s per chip (matrix unit)
    hbm_bw: float       # HBM bytes/s per chip
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "393 TOP/s int8, 16 GB HBM at 819 GB/s"),
}


def peaks(device_kind: str) -> Peaks:
    """The published peaks of ``device_kind``; raises for an unknown kind."""
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device_kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]
