"""The host's part of a decode step: ``pool.step()`` less the time it blocked
on the step's result (``kv.step.fetch``) — CoW checks, three uploads, the
dispatch and the bookkeeping of one host round trip a token. Median of
``step_host_ms`` over the window's requests; None where spans lack it."""

from benchmark import reduce


def read(obs):
    steps = [ms for _, s in reduce.joined(obs) for ms in s.get("step_host_ms", [])]
    return reduce.median(steps) if steps else None
