"""Roofline share of a decode step's sparse attention: K and V of the SELECTED
rows read once (1,024 values a row) with q and o of the live slots, and
``4 x 32 x 128`` FLOPs a selected row (``work_keye_vl.attend_work`` at the
traced section's means, a layer a step) over the traced time of the decode
program's gather and attention: the loop that takes one live slot a trip,
gathers its selected K and V rows (XLA's gather) and attends to them. It is
a ``while`` operation, known by what it carries: the slots' outputs
``bf16[slots, heads x head_dim]`` (the trace gives a loop the time of
everything it runs; a dead slot makes no trip). None where the trace holds
no such loop."""

from benchmark import work, work_keye_vl


def read(obs):
    peaks, mean = obs.get("peaks"), work_keye_vl.observed_step(obs)
    if not peaks or mean is None:
        return None
    m = obs["family"]["shapes"]
    carried = "bf16[%d,%d]" % (m["slots"], m["heads"] * m["head_dim"])
    spent = sum(sec for name, sec in obs["trace"]["device_ops"]
                if name.startswith("while:") and carried in name) if obs.get("trace") else 0
    if not spent:
        return None
    flops, nbytes = work_keye_vl.attend_work(
        m, live_slots=mean["live_slots"], selected_rows=mean["selected_rows"])
    return (100.0 * mean["steps"] * m["layers"]
            * work.least_seconds(flops, nbytes, peaks) / spent)
