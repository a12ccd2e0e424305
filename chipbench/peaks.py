"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Copied from ``benchmarks/common.py`` (``DEVICE_PEAKS``) so that the
yardstick stays with the benchmark.  Source: Google Cloud documentation,
"TPU v5e": 197 TFLOP/s bf16 and 819 GB/s of HBM bandwidth per chip.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """Peak bf16 FLOP/s and HBM bytes/s of one chip.  A device kind
    without a published entry is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}: add them to chipbench/peaks.py "
                         f"with their source") from None
