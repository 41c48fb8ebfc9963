"""Published peaks of each chip the benchmark runs on, keyed by
``device_kind`` as JAX reports it.  A device that is not in the table is
an error, never a default.

The benchmark keeps its own table so that a change to the program
cannot move the yardstick.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float       # FLOP/s
    hbm_bw: float           # B/s
    source: str


# Google Cloud documentation, "TPU v5e" (system architecture): 197
# TFLOP/s bf16, 16 GB of HBM2 at 819 GB/s.
PEAKS = {
    "TPU v5 lite": Peaks(flops_bf16=197e12, hbm_bw=819e9,
                         source="Google Cloud documentation, TPU v5e"),
}


def peaks_of(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
