"""``kv.step.prepare`` a step, median, no profiler listening: what
``dispatch()`` does before its uploads (which slots step, copy-on-write, the
windows slid, the tokens the host knows, the counters). From the ``untraced``
segment of ``step_account``; None where absent."""

from benchmark import stepaccount


def read(obs):
    return stepaccount.p50_ms(obs, "phases_ms", "kv.step.prepare")
