"""Published peaks of the chips the benchmark runs on, keyed by
`jax.Device.device_kind`. A kind that is not here is an error.

Source: Google Cloud documentation, "TPU v5e" (system architecture): one
chip has 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    flops_bf16: float     # FLOP/s
    hbm_bytes: float      # bytes/s
    hbm_capacity: float   # bytes
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(flops_bf16=197e12, hbm_bytes=819e9,
                         hbm_capacity=16e9,
                         source='Google Cloud documentation, "TPU v5e"'),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r} "
                         f"(known: {sorted(PEAKS)})") from None
