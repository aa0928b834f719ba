"""Share of chip 0's busy time inside collective operations (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all), from the trace.
Total, not exposed: overlap with compute is not subtracted."""

def read(obs):
    tr = obs.get("trace")
    if not tr or not tr["busy0_s"] or not tr["collective_s"]:
        return None
    return 100.0 * tr["collective_s"] / tr["busy0_s"]
