"""Client latency (sent to full answer) minus the executor's own span
(queue + prefill + decode) of the same request id: HTTP, JSON and thread
hand-offs at the door. Median over the window's requests."""

from benchmark import reduce


def read(obs):
    rows = reduce.joined(obs)
    if not rows:
        return None
    return reduce.median([
        (rec["done"] - rec["sent"]) * 1e3
        - 1e3 * sum(span["phases"].get(k, 0.0) for k in ("queue", "prefill", "decode"))
        for rec, span in rows])
