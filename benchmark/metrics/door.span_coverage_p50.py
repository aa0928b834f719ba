"""How much of the client's latency (sent to full answer) the request's span
accounts for: the sum of ALL its phases over ``done - sent``, median. What is
missing is outside the handler: connect, thread spawn, the client's own read.
None where the spans are not a closed account (no ``t_start``)."""

from benchmark import reduce


def read(obs):
    cover = [100.0 * sum(s["phases"].values()) / (rec["done"] - rec["sent"])
             for rec, s in reduce.joined(obs)
             if "t_start" in s and rec["done"] > rec["sent"]]
    return reduce.median(cover) if cover else None
