"""The work the ``keye_vl`` family REQUIRES of a decode step, from its shapes
and the step's own counters: what ``step.mfu.decode.dsa`` and the two
``dsa.*_roofline.decode`` shares divide by. ``shapes`` is
``benchmark/models/keye_vl.py:shapes``; bytes are of the weights' dtype
(``weight_bytes``), the router's float32. A cached index key counts its 64
VALUES, not the 128 lanes it occupies; K/V rows count only where the
selection chose them; an expert counts only where a live token chose it
(``touched``). The peaks and ``least_seconds`` are ``benchmark/work.py``'s."""

from __future__ import annotations

from typing import Optional, Tuple


def attention_params(m: dict) -> int:
    """Wq, Wk, Wv, Wo of one layer."""
    D, hd = m["hidden"], m["head_dim"]
    return 2 * D * m["heads"] * hd + 2 * D * m["kv_heads"] * hd


def indexer_params(m: dict) -> int:
    """WqI, WkI and the head weights' Ww of one layer."""
    return m["hidden"] * (m["index_heads"] * m["index_dim"] + m["index_dim"]
                          + m["index_heads"])


def expert_params(m: dict) -> int:
    return 3 * m["hidden"] * m["expert_width"]


def per_step(m: dict, counters: dict, cumulative: dict = None) -> Optional[dict]:
    """Means a decode step from the pool's counters over some span of steps
    (``steps``: how many): live slots, live cached rows (block-rounded lengths less the half block
    an average slot overshoots by), experts touched and token-expert pairs
    computed, both summed over the layers; and the K/V rows attention read a
    step a layer, ``live_rows`` times the share of scored rows that
    ``cumulative`` (the pool's ``block_stats()`` over the whole run:
    ``dsa_selected_rows`` over ``dsa_live_rows``) says were selected."""
    each = m["resident_experts"] * m["layers"]
    if not counters or not counters.get("moe_experts_resident") or not each:
        return None
    n = counters["moe_experts_resident"] / each      # steps the counters saw
    live = counters["moe_routed_tokens"] / m["layers"] / n
    rows = max(0.0, counters["kv_blocks_read"] / n - live / 2) * m["block_T"]
    share = None
    if cumulative and cumulative.get("dsa_live_rows"):
        share = cumulative["dsa_selected_rows"] / cumulative["dsa_live_rows"]
    return {"steps": n, "live_slots": live, "live_rows": rows,
            "selected_rows": None if share is None else rows * share,
            "touched": counters["moe_experts_touched"] / n,
            "assignments": counters["moe_resident_assignments"] / n}


def observed_step(obs: dict) -> Optional[dict]:
    """``per_step`` of a run's observation: the pool's counters over the
    traced section, the whole run's selected share from ``/stats``. None
    where the run is not this family's, or was not traced, or the program
    does not count the selection."""
    fam = obs.get("family")
    if not fam or "index_heads" not in fam.get("shapes", {}):
        return None
    blocks = ((obs.get("serve") or {}).get("executor_stats") or {}).get("blocks")
    mean = per_step(fam["shapes"], fam.get("traced_counters"), blocks)
    return mean if mean and mean["selected_rows"] is not None else None


def traced_seconds(obs: dict, shapes) -> float:
    """Seconds the traced window's device operations took whose name holds
    one of ``shapes`` (an XLA operation is known by the shapes it writes)."""
    ops = (obs.get("trace") or {}).get("device_ops") or []
    return sum(sec for name, sec in ops if any(s in name for s in shapes))


def select_work(m: dict, *, live_slots: float, live_rows: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of one layer's index scores and top-k in a decode step:
    the index key of every live row read once (64 values), ``2 x heads x 64``
    FLOPs a row; the slots' index queries and head weights."""
    HI, dI, wb = m["index_heads"], m["index_dim"], m["weight_bytes"]
    flops = 2.0 * HI * dI * live_rows
    nbytes = live_rows * dI * wb + live_slots * HI * (dI * wb + 4)
    return flops, nbytes


def attend_work(m: dict, *, live_slots: float, selected_rows: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of one layer's gather and attention in a decode step:
    K and V of the SELECTED rows read once (``2 x kv_heads x head_dim``
    values a row), ``4 x heads x head_dim`` FLOPs a row; q and o of the live
    slots."""
    H, KV, hd, wb = m["heads"], m["kv_heads"], m["head_dim"], m["weight_bytes"]
    flops = 4.0 * H * hd * selected_rows
    nbytes = selected_rows * 2 * KV * hd * wb + live_slots * 2 * H * hd * wb
    return flops, nbytes


def expert_matmul_work(m: dict, *, touched: float, assignments: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of a step's expert matmuls: three matrices of every
    touched expert read once, 2 FLOPs a weight a token-expert pair."""
    w = expert_params(m)
    return 2.0 * assignments * w, touched * w * m["weight_bytes"]


def decode_step_work(m: dict, *, live_slots: float, live_rows: float,
                     selected_rows: float, touched: float,
                     assignments: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of one whole decode step: attention's and the indexer's
    weights of every layer and the head read once, the router (float32), the
    touched experts, the live index keys and the selected K/V rows in every
    layer; 2 FLOPs a weight a live token."""
    L, wb = m["layers"], m["weight_bytes"]
    always = L * (attention_params(m) + indexer_params(m)) + m["hidden"] * m["vocab"]
    router = L * m["hidden"] * m["router_width"]
    ef, eb = expert_matmul_work(m, touched=touched, assignments=assignments)
    sf, sb = select_work(m, live_slots=live_slots, live_rows=live_rows)
    af, ab = attend_work(m, live_slots=live_slots, selected_rows=selected_rows)
    flops = 2.0 * live_slots * (always + router) + ef + L * (sf + af)
    nbytes = always * wb + router * 4 + eb + L * (sb + ab)
    return flops, nbytes
