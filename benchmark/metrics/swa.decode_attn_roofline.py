"""Roofline share of the paged decode attention kernel under grouped heads and
a window: K and V of the rows a query SEES read once (1,024 lanes each) with q
and o of the live slots (``work_trinity.decode_attn_work`` at the traced
section's means: a full layer's rows for the full layers' calls, the window's
for the sliding layers') over the traced time of the Mosaic calls named
``paged_decode_attn``, one a layer a step. None where the trace has no such
call or the program keeps no windowed cache group."""

from benchmark import work, work_trinity


def read(obs):
    tr, peaks, mean = obs.get("trace"), obs.get("peaks"), work_trinity.observed_step(obs)
    if not tr or not peaks or mean is None:
        return None
    m = obs["family"]["shapes"]
    mine = [agg for name, agg in tr["mosaic_calls"].items()
            if work_trinity.DECODE_KERNEL in name]
    spent = sum(agg["seconds"] for agg in mine)
    if not spent:
        return None
    least = {kind: work.least_seconds(*work_trinity.decode_attn_work(
        m, live_slots=mean["live_slots"], rows=mean[kind]), peaks)
        for kind in ("full_rows", "window_rows")}
    full = m["full_layers"] / m["layers"]       # the share of calls that are a full layer's
    a_call = full * least["full_rows"] + (1 - full) * least["window_rows"]
    return 100.0 * sum(agg["calls"] for agg in mine) * a_call / spent
