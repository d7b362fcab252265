"""Peak rates of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind not in the table is an error, never
another chip's peaks.

TPU v5e (JAX: "TPU v5 lite"), per chip, from Google Cloud's "TPU v5e"
documentation: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at
819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s
    hbm_bytes: float         # bytes/s
    hbm_capacity: float      # bytes


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes=819e9,
                         hbm_capacity=16e9),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
