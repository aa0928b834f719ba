"""Model FLOP/s utilisation: required forward + backward FLOPs per item
(``work.train_flops_per_step``) x items/s/chip over the chip's bf16 peak.
Recomputation is not counted. The rate is the median segment's: this metric
is read in the traced run, whose whole-window rate holds the tracer's stalls."""


def read(obs):
    train, peaks = obs.get("train"), obs.get("peaks")
    if not train or not peaks:
        return None
    return (100.0 * train["flops_per_item"] * train["items_s_chip_median_segment"]
            / peaks["bf16_flops_per_s"])
