"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A kind not in the table is an error, never a default: a share of a
peak is only worth reporting against the chip it was measured on.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float           # FLOP/s
    hbm_bytes_per_s: float      # B/s
    hbm_bytes: float            # B
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "393 TOP/s int8, 16 GB of HBM at 819 GB/s"),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise LookupError(f"no published peaks for device_kind "
                          f"{device_kind!r} (known: {sorted(PEAKS)})") from None
