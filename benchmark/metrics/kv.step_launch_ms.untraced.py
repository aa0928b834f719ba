"""``kv.step.dispatch`` a step, median, no profiler listening: the launch of
the one decode program (its ~600 operands handed over). From the ``untraced``
segment of ``step_account``; None where absent."""

from benchmark import stepaccount


def read(obs):
    return stepaccount.p50_ms(obs, "phases_ms", "kv.step.dispatch")
