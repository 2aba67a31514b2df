"""Published peaks of the chips this benchmark may run on, keyed by the
`device_kind` string JAX reports. A kind that is not listed raises: a number
divided by some other chip's peak is not a measurement.

Source: Google Cloud TPU documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s per chip. "TPU v5 lite" is what
the v5e machine answers (PERF.md, PR 21).

Copied from the program's `obs/attribution.CHIP_SPECS` so that a later PR can
change the program and not the yardstick.
"""

from __future__ import annotations

from typing import NamedTuple


class Peak(NamedTuple):
    flops_per_s: float       # bf16 matmul peak of one chip
    hbm_bytes_per_s: float   # HBM bandwidth of one chip
    source: str


_V5E = Peak(197e12, 819e9, "Google Cloud TPU docs, TPU v5e system architecture")

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak for device_kind {device_kind!r} in "
            f"benchmark/lib/peaks.py (known: {sorted(PEAKS)}); add the chip "
            f"with its source before measuring on it") from None
