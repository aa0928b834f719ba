"""What the door itself costs a request: the span's ``read + parse + handoff
+ serialize + write``, measured on the handler thread. Median over the
window's 200s; None where the spans carry no door phases."""

from benchmark import reduce

DOOR = ("read", "parse", "handoff", "serialize", "write")


def read(obs):
    door = [1e3 * sum(s["phases"][k] for k in DOOR)
            for _, s in reduce.joined(obs) if all(k in s["phases"] for k in DOOR)]
    return reduce.median(door) if door else None
