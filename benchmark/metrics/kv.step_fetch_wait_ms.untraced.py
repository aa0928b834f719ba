"""``kv.step.fetch`` a step, median, no profiler listening: the host waiting
for the device (near nothing where the host is the slower side). From the
``untraced`` segment of ``step_account``; None where absent."""

from benchmark import stepaccount


def read(obs):
    return stepaccount.p50_ms(obs, "phases_ms", "kv.step.fetch")
