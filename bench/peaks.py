"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" —
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s
of chip-to-chip interconnect.  A kind that is not in the table is an
error: a roofline against another chip's peaks would be silently wrong.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,        # FLOP/s per chip
        "int8_ops": 393e12,          # OP/s per chip
        "hbm_bytes_s": 819e9,        # bytes/s
        "hbm_bytes": 16e9,           # bytes
        "ici_bytes_s": 200e9,        # 1,600 Gbit/s per chip
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
