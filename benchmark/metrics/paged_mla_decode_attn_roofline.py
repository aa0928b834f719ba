"""Roofline share of the absorbed latent-attention kernel: the least time its
calls require (``work_kimi_k2.mla_call_work`` at the traced section's mean
live slots and live cached rows) over the traced time of the Mosaic calls
named ``paged_mla_decode_attn``. None where the trace has no such call."""

from benchmark import work, work_kimi_k2

KERNEL = "paged_mla_decode_attn"


def read(obs):
    tr, peaks, fam = obs.get("trace"), obs.get("peaks"), obs.get("family")
    if not tr or not peaks or not fam:
        return None
    mine = [agg for name, agg in tr["mosaic_calls"].items() if KERNEL in name]
    mean = work_kimi_k2.per_step(fam["shapes"], fam.get("traced_counters"))
    spent = sum(agg["seconds"] for agg in mine)
    if not spent or mean is None:
        return None
    flops, nbytes = work_kimi_k2.mla_call_work(
        fam["shapes"], live_slots=mean["live_slots"], live_rows=mean["live_rows"])
    calls = sum(agg["calls"] for agg in mine)
    return 100.0 * calls * work.least_seconds(flops, nbytes, peaks) / spent
