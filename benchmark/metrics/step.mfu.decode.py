"""A decode step's least time by the roofline (``work_kimi_k2.decode_step_work``
at the traced section's mean live slots, live cached rows and touched
experts; the larger of FLOPs over the bf16 peak and bytes over the HBM peak)
over the median DEVICE time of a step in the trace. None where the run holds
no traced steps or the program counts no experts."""

from benchmark import reduce, work, work_kimi_k2


def read(obs):
    fam, peaks = obs.get("family"), obs.get("peaks")
    if not fam or not peaks or not fam.get("step_device_s"):
        return None
    mean = work_kimi_k2.per_step(fam["shapes"], fam.get("traced_counters"))
    if mean is None:
        return None
    flops, nbytes = work_kimi_k2.decode_step_work(fam["shapes"], **mean)
    return (100.0 * work.least_seconds(flops, nbytes, peaks)
            / reduce.median(fam["step_device_s"]))
