"""``sched.gauges`` a step, median, no profiler listening: the pool's
``block_stats()`` mirrored into the registry and the spool hook, every step
(ROADMAP D6 asks whether it should be). From the ``untraced`` segment of
``step_account``; None where absent."""

from benchmark import stepaccount


def read(obs):
    return stepaccount.p50_ms(obs, "phases_ms", "sched.gauges")
