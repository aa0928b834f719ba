"""Median decode step of the slot pool, host clock around the blocking step
(``step_ms`` of every request's span)."""

from benchmark import reduce


def read(obs):
    steps = [ms for _, s in reduce.joined(obs) for ms in s.get("step_ms", [])]
    return reduce.median(steps) if steps else None
