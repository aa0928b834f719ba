"""A decode step's least time by the roofline (``work_trinity.decode_step_work``
at the traced section's mean live slots, visible cached rows a full and a
sliding layer, and touched experts; the larger of FLOPs over the bf16 peak and
bytes over the HBM peak) over the median DEVICE time of a step in the trace:
the share of the whole step. None where the run holds no traced steps or the
program keeps no windowed cache group."""

from benchmark import reduce, work, work_trinity


def read(obs):
    fam, peaks, mean = obs.get("family"), obs.get("peaks"), work_trinity.observed_step(obs)
    if not peaks or mean is None or not fam.get("step_device_s"):
        return None
    mean.pop("steps")
    flops, nbytes = work_trinity.decode_step_work(fam["shapes"], **mean)
    return (100.0 * work.least_seconds(flops, nbytes, peaks)
            / reduce.median(fam["step_device_s"]))
