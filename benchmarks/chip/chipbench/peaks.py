"""Published peaks of each accelerator the benchmark may run on, keyed by
JAX's ``device_kind``. A device that is not here is an error, not a default.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flop_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


_V5E = Peaks(
    bf16_flop_per_s=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
    source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
           "16 GB HBM at 819 GB/s per chip")

PEAKS = {
    "TPU v5 lite": _V5E,     # what JAX reports for a v5e chip
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
