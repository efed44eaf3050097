"""Published peak rates of the chips the benchmark may run on.

The yardstick, kept with the benchmark so that a change to the program
cannot move it.  Keyed by ``device_kind`` exactly as JAX reports it; a
device that is not in the table is an error, never a default.
"""

from __future__ import annotations

from typing import NamedTuple


class ChipPeak(NamedTuple):
    bf16_flops_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: float


PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
    # HBM at 819 GB/s per chip.  device_kind as the chip reports it.
    "TPU v5 lite": ChipPeak(197e12, 819e9, 16e9),
}


def peak(device_kind: str) -> ChipPeak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} (benchmark/peaks.py)") from None
