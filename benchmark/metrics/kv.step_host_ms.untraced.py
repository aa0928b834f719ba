"""The host's own work a step with no profiler listening: the median over
the ``untraced`` rows of ``step_account`` of a row's period less its
``kv.step.fetch`` (the wait for the device). ``kv.step_host_ms`` reads the
same difference from the request spans of a TRACED run, a quarter to a half
of which is the tracer's. None where the program keeps no account."""

from benchmark import stepaccount


def read(obs):
    return stepaccount.p50_ms(obs, "host_ms")
