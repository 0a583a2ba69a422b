"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.  A
one-chip TPU v5e reports itself as "TPU v5 lite".
"""
from __future__ import annotations

from dataclasses import dataclass

__all__ = ["Peak", "PEAKS", "peak_for"]


@dataclass(frozen=True)
class Peak:
    bf16_flops: float       # FLOP/s
    hbm_bytes_per_s: float  # bytes/s
    hbm_bytes: float        # bytes
    source: str


PEAKS = {
    "TPU v5 lite": Peak(197e12, 819e9, 16e9,
                        "Google Cloud documentation, TPU v5e"),
}


def peak_for(device_kind: str) -> Peak:
    """The chip's peaks; a kind that is not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak table entry for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
