"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture): 197
TFLOP/s bf16, 16 GB of HBM2e at 819 GB/s per chip. A device that is not in
the table is an error, never a default.
"""

from __future__ import annotations

from typing import Dict

_V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
# the v5e reports itself as "TPU v5 lite" (jax 0.9.0 / libtpu 0.0.34)
PEAKS: Dict[str, Dict[str, float]] = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks_for(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(
            f"device_kind {device_kind!r} has no entry in benchmarks/harness/"
            "peaks.py: add its published peaks with their source")
    return PEAKS[device_kind]
