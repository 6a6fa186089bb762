"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip,
1,600 Gbit/s inter-chip interconnect. A kind that is not in the table is an
error, never a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float      # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: float


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                         hbm_bytes=16e9),
}


def peaks_for(kind: str) -> Peaks:
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
