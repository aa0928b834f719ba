"""What a token costs with no profiler listening: the median of the serving
loop's period a collected step (collect to collect), from the ``untraced``
segment of ``/stats``' ``step_account``. None where the program keeps no
account or the segment holds under 200 steps."""

from benchmark import stepaccount


def read(obs):
    return stepaccount.p50_ms(obs, "period_ms")
