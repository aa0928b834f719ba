"""``sched.retire`` a step (exclusive), median, no profiler listening: the
step's tokens handed to their requests, finished and expired ones evicted.
From the ``untraced`` segment of ``step_account``; None where absent."""

from benchmark import stepaccount


def read(obs):
    return stepaccount.p50_ms(obs, "phases_ms", "sched.retire")
