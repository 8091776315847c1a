"""The work an algorithm needs, counted from shapes: the operations and
bytes behind the roofline and utilization metrics.  They count what the
method requires, the same whatever implements it, so an implementation
that moves fewer bytes or recomputes less cannot read over 100%.
"""
from __future__ import annotations

from typing import Dict


def sdca_leaf_call(m_b: int, d: int, H: int, itemsize: int = 4
                   ) -> Dict[str, float]:
    """One leaf solve of H coordinate steps on an (m_b, d) block.

    Bytes: the block X, its y, ||x||^2/(lam m) and alpha read once, alpha
    written once, w read and dw written once.  FLOPs: 4 d per coordinate
    step (the w.x_i dot and the rank-1 update of w)."""
    bytes_ = itemsize * (m_b * d + 4 * m_b + 2 * d)
    return {"bytes": float(bytes_), "flops": float(4 * d * H)}


def sdca_round(topology: dict, d: int, itemsize: int = 4) -> Dict[str, float]:
    """The leaf solves of one root round of a two-level tree: every leaf,
    once per group round."""
    leaves = int(topology["n_groups"]) * int(topology["workers_per_group"])
    calls = leaves * int(topology["group_rounds"])
    one = sdca_leaf_call(int(topology["m_per_worker"]), d,
                         int(topology["local_steps"]), itemsize)
    return {k: v * calls for k, v in one.items()}


def roofline_s(work: Dict[str, float], peaks: dict) -> Dict[str, float]:
    """The least time the chip could take, and which bound sets it."""
    t_flops = work["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "bytes" if t_bytes >= t_flops else "flops"}


def dual_round_flops(m: int, d: int, topology: dict) -> float:
    """FLOPs one root round needs: the leaf solves plus the gap's three
    O(m d) passes (X^T alpha, the margins X w, each 2 m d)."""
    return sdca_round(topology, d)["flops"] + 2 * 2.0 * m * d


def lm_matmul_params(model: dict) -> int:
    """Parameters that take part in a matmul per token: the attention and
    MLP projections of every layer and the output head (the embedding
    lookup excluded)."""
    d, h, kv = model["d_model"], model["num_heads"], model["num_kv_heads"]
    hd = d // h
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = 3 * d * model["d_ff"]
    return model["num_layers"] * (attn + mlp) + d * model["vocab_size"]


def lm_flops_per_token(model: dict, seq: int) -> float:
    """Training FLOPs per token: 6 N for the matmul parameters, plus the
    causal (and windowed) attention: QK^T and PV are 4 (h hd) FLOPs per
    key attended in the forward pass, 12 with the backward.  Nothing
    recomputed is counted."""
    window = model.get("window") or seq
    ctx = sum(min(i + 1, window) for i in range(seq)) / seq
    hd_all = model["d_model"]            # query heads x head size
    attn = 12.0 * model["num_layers"] * hd_all * ctx
    return 6.0 * lm_matmul_params(model) + attn
