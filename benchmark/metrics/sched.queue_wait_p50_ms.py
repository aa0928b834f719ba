"""Median wait in the executor's queue before admission (``request_span``)."""

from benchmark import reduce


def read(obs):
    rows = reduce.joined(obs)
    return reduce.median([1e3 * s["phases"].get("queue", 0.0) for _, s in rows]) if rows else None
