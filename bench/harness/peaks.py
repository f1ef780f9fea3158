"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture table):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s. A device kind that
is not in the table is an error: a share of another chip's peaks would be a
wrong number, not an approximate one.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float     # FLOP/s
    int8_ops: float       # OP/s
    hbm_bw: float         # bytes/s
    hbm_bytes: float      # bytes of device memory


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, int8_ops=393e12, hbm_bw=819e9,
                         hbm_bytes=16e9),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"the table has {sorted(PEAKS)}") from None
