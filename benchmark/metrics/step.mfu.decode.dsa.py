"""A decode step's least time by the roofline (``work_keye_vl.decode_step_work``
at the traced section's mean live slots, live cached rows, selected rows and
touched experts; the larger of FLOPs over the bf16 peak and bytes over the HBM
peak) over the median DEVICE time of a step in the trace. None where the run
holds no traced steps or the program counts no selection."""

from benchmark import reduce, work, work_keye_vl


def read(obs):
    fam, peaks, mean = obs.get("family"), obs.get("peaks"), work_keye_vl.observed_step(obs)
    if not peaks or mean is None or not fam.get("step_device_s"):
        return None
    mean.pop("steps")
    flops, nbytes = work_keye_vl.decode_step_work(fam["shapes"], **mean)
    return (100.0 * work.least_seconds(flops, nbytes, peaks)
            / reduce.median(fam["step_device_s"]))
