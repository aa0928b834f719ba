"""Peak bytes in use on the fullest chip over its bytes limit
(``memory_stats()``), set-up included."""

from benchmark import reduce


def read(obs):
    return reduce.peak_mem_frac(obs)
