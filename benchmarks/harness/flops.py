"""Operations and bytes, from shapes. The yardstick's arithmetic: no PR that
claims a gain can change it.

``shapes`` is what a family's ``shapes(config, cell)`` returns.
"""

from __future__ import annotations

from typing import Any, Dict


def train_flops_per_token(shapes: Dict[str, Any]) -> float:
    """Forward + backward operations one trained token REQUIRES: 6 per
    parameter (every parameter, the tied embedding once: it is the LM head's
    matmul) plus 12·L·S·d for attention's two S×S matmuls, uncut by causality
    (the convention of bench.py, PaLM's appendix B and nanoGPT, kept so the
    number compares). Recomputed operations (remat) do not count."""
    return (6.0 * shapes["params"]
            + 12.0 * shapes["n_layer"] * shapes["seq_len"] * shapes["d_model"])


def tokens_per_step(shapes: Dict[str, Any]) -> int:
    return shapes["per_chip_batch"] * shapes["chips"] * shapes["seq_len"]


def attention_call(shapes: Dict[str, Any], backward: bool) -> Dict[str, float]:
    """Least operations and HBM bytes of ONE causal flash-attention call on one
    device's shard ``[B, H, S, hd]``. Forward: QK^T and PV, 2·S²·hd
    multiply-adds each per head, halved by the causal mask -> 2·B·H·S²·hd
    FLOPs; reads q, k, v, writes o and the f32 log-sum-exp. Backward: five
    such matmuls (QK^T again, dV, dP, dQ, dK) -> 5·B·H·S²·hd; reads q, k, v,
    o, do and lse, writes dq, dk, dv."""
    b, h = shapes["per_chip_batch"], shapes["n_head"]
    s, hd, w = shapes["seq_len"], shapes["head_dim"], shapes["attention_dtype_bytes"]
    bhs = float(b * h * s)
    if backward:
        return {"flops": 5.0 * bhs * s * hd, "bytes": 8.0 * bhs * hd * w + 4.0 * bhs}
    return {"flops": 2.0 * bhs * s * hd, "bytes": 4.0 * bhs * hd * w + 4.0 * bhs}


def roofline_seconds(work: Dict[str, float], peaks: Dict[str, float]) -> Dict[str, Any]:
    """The least time the chip could take, and which bound sets it."""
    compute = work["flops"] / peaks["bf16_flops_per_s"]
    memory = work["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
