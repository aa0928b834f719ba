"""How late the load generator sent a request against its due time, p99:
a starved generator must not be read as a fast server."""

from benchmark import loadgen


def read(obs):
    late = (obs.get("serve") or {}).get("window", {}).get("lateness_ms")
    return loadgen.percentile(late, 99) if late else None
