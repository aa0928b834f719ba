"""Small reductions the metric readers share."""

from __future__ import annotations

import statistics

from benchmark import work


def median(values) -> float:
    return float(statistics.median(values))


def joined(obs):
    """(client record, executor span) of every in-window request answered
    200 whose span the flight recorder holds, joined on the request id."""
    serve = obs.get("serve")
    if not serve:
        return []
    spans = serve["spans"]
    return [(rec, spans[rec["id"]]) for rec in serve["window"]["records"]
            if rec["in_window"] and rec["ok"] and rec["id"] in spans
            and spans[rec["id"]].get("code") == 200]


def idle_share(obs):
    tr = obs.get("trace")
    return None if not tr else 100.0 * tr["idle_share"]


def peak_mem_frac(obs):
    mem = obs.get("memory")
    if not mem or not mem.get("limit"):
        return None
    return 100.0 * mem["peak"] / mem["limit"]


def mosaic_time_share(obs):
    tr = obs.get("trace")
    if not tr or not tr["busy0_s"] or not tr["mosaic_s"]:
        return None
    return 100.0 * tr["mosaic_s"] / tr["busy0_s"]


def flash_roofline(obs, kind: str):
    """Least seconds the calls of one flash kernel require over the seconds
    the trace gives them; None where the trace has no such call."""
    tr, peaks, train = obs.get("trace"), obs.get("peaks"), obs.get("train")
    if not tr or not peaks:
        return None
    causal = bool((train or {}).get("causal", True))
    least = spent = 0.0
    for name, agg in tr["mosaic_calls"].items():
        found = work.classify_flash_call(name)
        if not found or found[0] != kind:
            continue
        _, bh, t, d = found
        flops, nbytes = work.flash_call_work(kind, bh=bh, tq=t, tk=t, d=d,
                                             causal=causal)
        least += agg["calls"] * work.least_seconds(flops, nbytes, peaks)
        spent += agg["seconds"]
    return 100.0 * least / spent if spent else None
