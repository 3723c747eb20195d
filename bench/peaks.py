"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device kind missing from the table is an error, never a default: a
share of a peak computed against the wrong chip is no number at all.
"""

from __future__ import annotations

import dataclasses

__all__ = ["Peaks", "PEAKS", "peaks_for"]


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float      # dense bfloat16 operations per second
    int8_ops: float        # dense int8 operations per second
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e' system architecture "
               "(https://cloud.google.com/tpu/docs/v5e)"),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; raises ``KeyError`` for an unknown
    kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
