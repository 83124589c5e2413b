"""Published peaks per chip, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud TPU documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
inter-chip interconnect).  A device that is not listed is an error, not
a default.
"""
from __future__ import annotations

SOURCE = "Google Cloud TPU documentation, TPU v5e"

_V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9, "ici_bits_per_s": 1.6e12}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
