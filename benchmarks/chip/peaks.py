"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s,
1,600 Gbit/s inter-chip interconnect.  A device missing from the table is
an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add them to benchmarks/chip/peaks.py with a source")
    return PEAKS[device_kind]


def least_seconds(flops: float, nbytes: float, device_kind: str):
    """The least time the chip could take for the work: the larger of the
    FLOPs at the bf16 MXU peak and the bytes at HBM bandwidth.  Returns
    (seconds, "flops" | "bytes") — the bound that binds."""
    p = peaks(device_kind)
    tf, tb = flops / p["bf16_flops_per_s"], nbytes / p["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
