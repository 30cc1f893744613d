"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB of HBM at 819 GB/s.  JAX reports a v5e chip as
"TPU v5 lite".  A kind that is not in the table is an error, never a
default.
"""

from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops: float      # bf16 FLOP/s per chip
    hbm_bw: float     # HBM bytes/s per chip
    hbm_bytes: int    # HBM capacity per chip


PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16 * 10**9),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
