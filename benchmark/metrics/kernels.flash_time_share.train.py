"""Share of chip 0's busy time spent in Mosaic (flash) calls, from the trace."""

from benchmark import reduce


def read(obs):
    return reduce.mosaic_time_share(obs)
