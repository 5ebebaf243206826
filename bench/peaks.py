"""Published peaks of each chip the benchmark runs on, by ``device_kind``.

TPU v5e (JAX reports "TPU v5 lite"), one chip: 197 TFLOP/s in bf16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s (Google Cloud
documentation, "TPU v5e").  A device that is not listed is an error,
not a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/peaks.py "
                       f"with their source") from None
