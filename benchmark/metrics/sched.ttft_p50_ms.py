"""Median time to the first token INSIDE the server: queue + prefill of the
span. Nothing streams, so no client sees it yet."""

from benchmark import reduce


def read(obs):
    rows = reduce.joined(obs)
    if not rows:
        return None
    return reduce.median([1e3 * (s["phases"].get("queue", 0.0) + s["phases"].get("prefill", 0.0))
                          for _, s in rows])
