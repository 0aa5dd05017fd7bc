"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

TPU v5e (kind "TPU v5 lite"): Google Cloud documentation, "TPU v5e" —
197 TFLOP/s in bf16, 16 GB of HBM at 819 GB/s. A kind that is not in the
table is an error, never a default.
"""

from __future__ import annotations

PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,      # bf16
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {device_kind!r}; "
                         f"known kinds: {sorted(PEAKS)}") from None
