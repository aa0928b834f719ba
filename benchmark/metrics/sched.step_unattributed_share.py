"""What the loop thread's spans still miss of a step: 100 x the median
``other`` (a row's period less the exclusive time of every span that closed in
it) over the median period, ``untraced`` segment of ``step_account``. None
where absent."""

from benchmark import stepaccount


def read(obs):
    other = stepaccount.p50_ms(obs, "other_ms")
    period = stepaccount.p50_ms(obs, "period_ms")
    return 100.0 * other / period if other is not None and period else None
