"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture table):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, and 1,600 Gbit/s of
chip-to-chip interconnect per chip. A device kind that is not in the table is an
error: a roofline share against another chip's peaks would be a wrong number, not
an approximate one.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float         # FLOP/s
    int8_ops: float           # OP/s
    hbm_bw: float             # bytes/s
    ici_bw: float             # bytes/s of chip-to-chip interconnect, all links


#: ``device_kind`` as JAX reports it → published peaks.
PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, int8_ops=393e12, hbm_bw=819e9,
                         ici_bw=1600e9 / 8),
}

#: the chip this repository's projections and benchmarks target
V5E = "TPU v5 lite"


def peaks(device_kind: str) -> Peaks:
    """The published peaks of ``device_kind``; raises ``KeyError`` if unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"the table has {sorted(PEAKS)}") from None
