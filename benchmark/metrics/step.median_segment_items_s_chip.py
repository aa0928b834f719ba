"""Items/s/chip over the MEDIAN timed segment: the steadier statistic beside
the whole-window rate (a host stall moves one segment, not the median)."""

def read(obs):
    train = obs.get("train")
    if not train or not obs.get("peaks"):
        return None
    return train["items_s_chip_median_segment"]
