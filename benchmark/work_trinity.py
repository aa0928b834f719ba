"""The work the ``trinity`` family REQUIRES of a decode step, of a decode
attention call and of a windowed prefill attention call, from its shapes and
the pool's own counters: what ``step.mfu.decode.swa`` and the two
``swa.*_roofline`` shares divide by. ``shapes`` is
``benchmark/models/trinity.py:shapes``; bytes are of the weights' dtype
(``weight_bytes``), the router's float32. A cached row counts where a query
SEES it (a sliding layer: the window's rows, not the blocks mapped around
them), ``kv_heads x head_dim`` lanes for K and again for V; a (query, key)
pair counts where the masks let it through; an expert counts only where a
live token chose it (``touched``). The peaks and ``least_seconds`` are
``benchmark/work.py``'s."""

from __future__ import annotations

import re
from typing import Optional, Tuple

WINDOWED_PREFILL_KERNEL = "flash_fwd_swa"   # kernels/attention.py names it so
DECODE_KERNEL = "paged_decode_attn"
_SHAPE3 = re.compile(r"\[(\d+),(\d+),(\d+)\]")


def attention_params(m: dict) -> int:
    """Wq, Wo, Wgate (hidden x heads x head_dim) and Wk, Wv of one layer."""
    D, hd = m["hidden"], m["head_dim"]
    return 3 * D * m["heads"] * hd + 2 * D * m["kv_heads"] * hd


def swiglu_params(m: dict, width: int) -> int:
    return 3 * m["hidden"] * width


def per_step(m: dict, counters: dict) -> Optional[dict]:
    """Means a decode step from the pool's counters over some span of steps
    (``steps``: how many): live slots; the cached rows a query of a full layer
    sees, summed over live slots (block-rounded lengths less the half block
    an average slot overshoots by); the same for a sliding layer (the blocks
    from the first one the query still sees, less a whole block a slot: a
    window overshoots at both ends; never more than the window's rows, nor
    than the full layer's); resident experts touched and token-expert pairs
    computed, both summed over the expert layers."""
    each = m["resident_experts"] * m["sparse_layers"]
    if (not counters or not counters.get("moe_experts_resident") or not each
            or "kv_blocks_read_windowed" not in counters):
        return None
    n = counters["moe_experts_resident"] / each      # steps the counters saw
    live = counters["moe_routed_tokens"] / m["sparse_layers"] / n
    full = max(0.0, counters["kv_blocks_read"] / n - live / 2) * m["block_T"]
    window = max(0.0, counters["kv_blocks_read_windowed"] / n - live) * m["block_T"]
    return {"steps": n, "live_slots": live, "full_rows": full,
            "window_rows": min(window, full, live * m["window"]),
            "touched": counters["moe_experts_touched"] / n,
            "assignments": counters["moe_resident_assignments"] / n}


def observed_step(obs: dict) -> Optional[dict]:
    """``per_step`` of a run's observation: the pool's counters over the
    traced section. None where the run is not this family's, or was not
    traced, or the program keeps no windowed cache group."""
    fam = obs.get("family")
    if not fam or "window" not in fam.get("shapes", {}):
        return None
    return per_step(fam["shapes"], fam.get("traced_counters"))


def decode_attn_work(m: dict, *, live_slots: float, rows: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of one ``paged_decode_attn`` call over ``rows`` visible
    cached rows: K and V of every one read once (``2 x kv_heads x head_dim``
    values), ``4 x heads x head_dim`` FLOPs a row; q and o of the live slots."""
    H, G, hd, wb = m["heads"], m["kv_heads"], m["head_dim"], m["weight_bytes"]
    return 4.0 * H * hd * rows, rows * 2 * G * hd * wb + live_slots * 2 * H * hd * wb


def expert_matmul_work(m: dict, *, touched: float, assignments: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of a step's routed-expert matmuls: three matrices of
    every touched expert read once, 2 FLOPs a weight a token-expert pair."""
    w = swiglu_params(m, m["expert_width"])
    return 2.0 * assignments * w, touched * w * m["weight_bytes"]


def decode_step_work(m: dict, *, live_slots: float, full_rows: float,
                     window_rows: float, touched: float,
                     assignments: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of one whole decode step: the weights of every layer
    outside the routed experts and the head read once, the router (float32),
    the touched experts, the visible cached rows of every layer by its kind;
    2 FLOPs a weight a live token."""
    L, S, F, wb = m["layers"], m["sparse_layers"], m["full_layers"], m["weight_bytes"]
    always = (L * attention_params(m)
              + (L - S) * swiglu_params(m, m["dense_width"])
              + S * swiglu_params(m, m["expert_width"])      # the shared expert
              + m["hidden"] * m["vocab"])
    router = S * m["hidden"] * m["router_width"]
    ef, eb = expert_matmul_work(m, touched=touched, assignments=assignments)
    ff, fb = decode_attn_work(m, live_slots=live_slots, rows=full_rows)
    sf, sb = decode_attn_work(m, live_slots=live_slots, rows=window_rows)
    flops = 2.0 * live_slots * (always + router) + ef + F * ff + (L - F) * sf
    nbytes = always * wb + router * 4 + eb + F * fb + (L - F) * sb
    return flops, nbytes


def visible_pairs(n: int, window: Optional[int]) -> float:
    """(query, key) pairs of a causal sequence of ``n`` positions in which the
    query sees the key: ``j <= i`` and, with a window, ``i - j < window``."""
    if window is None or n <= window:
        return n * (n + 1) / 2.0
    return window * (window + 1) / 2.0 + (n - window) * float(window)


def prefill_attn_work(m: dict, *, n: int, window: Optional[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) one flash forward call requires of a prompt of ``n``
    positions: ``4 x head_dim`` FLOPs a visible pair a query head; q and o of
    every head, K and V of every K/V head, once."""
    H, G, hd, wb = m["heads"], m["kv_heads"], m["head_dim"], m["weight_bytes"]
    return (4.0 * hd * H * visible_pairs(n, window),
            2.0 * n * H * hd * wb + 2.0 * n * G * hd * wb)


def call_bucket(name: str) -> Optional[int]:
    """The sequence length of a flash forward call from its trace name, which
    carries the shape it writes: ``bf16[batch, T, heads x head_dim]``."""
    found = _SHAPE3.search(name)
    return int(found.group(2)) if found else None


def bucket_of(n: int, least: int) -> int:
    """The prefill bucket of a prompt of ``n`` tokens: the pool's ladder
    (``common.bucketing``: powers of two from ``least``)."""
    from deeplearning4j_tpu.common.bucketing import bucket_size

    return bucket_size(n, min_bucket=least)
