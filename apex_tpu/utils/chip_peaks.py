"""Published peak rates of the chips this repo has run on — the one
table behind the bandwidth fractions and roofline shares of the tools
(``tools/conv_attrib.py``; the benchmark keeps its own copy,
``benchmark/peaks.py``, which only a ``benchmark`` issue can merge).

Keyed by ``jax.devices()[0].device_kind`` exactly as the chip reports
it.  A device that is not in the table is an error, never a default: a
utilization against the wrong peak is worse than none.  Add a chip by
adding the string it reports and the source of its numbers.
"""

from __future__ import annotations

from typing import NamedTuple

import jax


class ChipPeak(NamedTuple):
    bf16_flops_per_s: float
    hbm_bytes_per_s: float


CHIP_PEAKS = {
    # TPU v5e — Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 819 GB/s HBM.  device_kind as reported on the chip (my chip run,
    # PR 21).
    "TPU v5 lite": ChipPeak(197e12, 819e9),
}


def chip_peak(device_kind: "str | None" = None) -> ChipPeak:
    """Peaks of ``device_kind`` (default: the first JAX device)."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(CHIP_PEAKS)} (apex_tpu/utils/chip_peaks.py)") from None
