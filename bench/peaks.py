"""Published peaks of each accelerator the benchmark may run on, keyed by the
``device_kind`` JAX reports. A device that is not listed is an error, never
a default: a share of a peak is only as good as the peak.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 394 TOP/s int8, 16 GiB HBM2 at 819 GB/s, and
1,600 Gbit/s of inter-chip interconnect (ICI) bandwidth.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float      # FLOP/s
    hbm_bytes: float       # bytes/s
    ici_bytes: float       # bytes/s per chip, all links together
    hbm_capacity: int      # bytes
    source: str


_V5E = Peaks(bf16_flops=197e12, hbm_bytes=819e9, ici_bytes=1600e9 / 8,
             hbm_capacity=16 * 2**30,
             source='Google Cloud documentation, "TPU v5e"')

TABLE = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


class UnknownDevice(LookupError):
    """The device kind has no entry in the peak table."""


def peaks_for(device_kind: str) -> Peaks:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(TABLE)}") from None
