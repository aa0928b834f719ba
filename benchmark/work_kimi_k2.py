"""The work the ``kimi_k2`` family REQUIRES of a decode step, from its shapes
and the step's own counters: what ``step.mfu.decode`` and the two roofline
shares of the cell divide by. ``shapes`` is ``benchmark/models/kimi_k2.py:
shapes``; bytes are of the weights' dtype (``weight_bytes``), the router's
float32. A cached row counts its 576 VALUES, not the 640 lanes it occupies;
an expert counts only where a live token chose it (``touched``). The peaks
and ``least_seconds`` are ``benchmark/work.py``'s."""

from __future__ import annotations

from typing import Optional, Tuple


def attention_params(m: dict) -> int:
    """Wdq, Wuq, Wdkv, Wukv, Wo of one layer."""
    D, H = m["hidden"], m["heads"]
    return (D * m["q_rank"] + m["q_rank"] * H * (m["nope"] + m["rope"])
            + D * (m["kv_rank"] + m["rope"])
            + m["kv_rank"] * H * (m["nope"] + m["v_dim"]) + H * m["v_dim"] * D)


def swiglu_params(m: dict, width: int) -> int:
    return 3 * m["hidden"] * width


def per_step(m: dict, counters: dict) -> Optional[dict]:
    """Means a decode step from the pool's counters over some span of steps:
    live slots, live cached rows (block-rounded lengths less the half block
    an average slot overshoots by), resident experts touched and
    token-expert pairs computed, both summed over the sparse layers."""
    each = m["resident_experts"] * m["sparse_layers"]
    if not counters or not counters.get("moe_experts_resident") or not each:
        return None
    n = counters["moe_experts_resident"] / each      # steps the counters saw
    live = counters["moe_routed_tokens"] / m["sparse_layers"] / n
    rows = max(0.0, counters["kv_blocks_read"] / n - live / 2) * m["block_T"]
    return {"live_slots": live, "live_rows": rows,
            "touched": counters["moe_experts_touched"] / n,
            "assignments": counters["moe_resident_assignments"] / n}


def mla_call_work(m: dict, *, live_slots: float, live_rows: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of one ``paged_mla_decode_attn`` call: every live row
    is read once (576 values), scores over 576 and values over 512 lanes for
    each of the heads; q (576) and o (512) of the live slots."""
    C, R, H, wb = m["kv_rank"], m["rope"], m["heads"], m["weight_bytes"]
    flops = 2.0 * H * live_rows * ((C + R) + C)
    nbytes = live_rows * (C + R) * wb + live_slots * H * ((C + R) + C) * wb
    return flops, nbytes


def expert_matmul_work(m: dict, *, touched: float, assignments: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of a step's routed-expert matmuls: three matrices of
    every touched expert read once, 2 FLOPs a weight a token-expert pair."""
    w = swiglu_params(m, m["expert_width"])
    return 2.0 * assignments * w, touched * w * m["weight_bytes"]


def decode_step_work(m: dict, *, live_slots: float, live_rows: float,
                     touched: float, assignments: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of one whole decode step: the weights of every layer
    outside the routed experts and the head read once, the touched experts,
    the live latent cache in every layer; 2 FLOPs a weight a live token."""
    L, S, wb = m["layers"], m["sparse_layers"], m["weight_bytes"]
    always = (L * attention_params(m)
              + (L - S) * swiglu_params(m, m["dense_width"])
              + S * swiglu_params(m, m["expert_width"])      # the shared expert
              + m["hidden"] * m["vocab"])
    router = S * m["hidden"] * m["router_width"]
    ef, eb = expert_matmul_work(m, touched=touched, assignments=assignments)
    af, ab = mla_call_work(m, live_slots=live_slots, live_rows=live_rows)
    flops = 2.0 * live_slots * (always + router) + ef + L * af
    nbytes = always * wb + router * 4 + eb + L * ab
    return flops, nbytes
