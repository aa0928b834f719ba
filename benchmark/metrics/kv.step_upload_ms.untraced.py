"""``kv.step.upload`` a step, median, no profiler listening: private copies
of the block tables, the tokens and the positions handed to the device. From
the ``untraced`` segment of ``step_account``; None where absent."""

from benchmark import stepaccount


def read(obs):
    return stepaccount.p50_ms(obs, "phases_ms", "kv.step.upload")
