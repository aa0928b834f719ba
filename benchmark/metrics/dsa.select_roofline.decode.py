"""Roofline share of a decode step's selection: the index key of every live
row read once (64 values) and ``2 x 16 x 64`` FLOPs a row
(``work_keye_vl.select_work`` at the traced section's means, a layer a step)
over the traced time of the decode program's index scores and top-k. They are
XLA operations, known by the shapes they write: the gather of every slot's
mapped index-key blocks (``[slots x max_blocks, block_T, 128]``) and whatever
is ``[slots, max_len]`` wide: the scores, their mask, the places of the rows
and the ONE sort that orders them with the rows' places as payload. Dead
slots and unmapped blocks are gathered and sorted too, which the required
work does not count: the share reads low for it. None where the trace holds no such operation."""

from benchmark import work, work_keye_vl


def read(obs):
    peaks, mean = obs.get("peaks"), work_keye_vl.observed_step(obs)
    if not peaks or mean is None:
        return None
    m = obs["family"]["shapes"]
    S, R, bT = m["slots"], m["max_len"], m["block_T"]
    spent = work_keye_vl.traced_seconds(
        obs, ("[%d,%d]" % (S, R), "[%d,%d,128]" % (S * R // bT, bT)))
    if not spent:
        return None
    flops, nbytes = work_keye_vl.select_work(
        m, live_slots=mean["live_slots"], live_rows=mean["live_rows"])
    return (100.0 * mean["steps"] * m["layers"]
            * work.least_seconds(flops, nbytes, peaks) / spent)
