"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A kind missing here is an error, never a default.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, HBM2 at 819 GB/s.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s
    hbm_bytes_per_s: float   # bytes/s


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_per_s=819e9),
}


class UnknownDevice(RuntimeError):
    pass


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device_kind {device_kind!r} has no entry in the peaks table "
            f"(known: {sorted(PEAKS)})") from None
