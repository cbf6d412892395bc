"""Published per-chip peaks, keyed by JAX's ``device_kind``.

Copied from ``benchmarks/roofline.py``. Source: Google Cloud documentation,
"TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of interconnect
(4 links, 50 GB/s each). A device that is not in the table is an error.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them to PEAKS with their "
                         "source")
    return PEAKS[device_kind]
