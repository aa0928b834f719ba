"""1 - (union of device-operation intervals / traced window), averaged over
the chips used."""

from benchmark import reduce


def read(obs):
    return reduce.idle_share(obs)
