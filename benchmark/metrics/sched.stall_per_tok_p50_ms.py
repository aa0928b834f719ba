"""Time a request stood still in its slot for each token it got: the span's
``interleave`` (other requests' prefills on the one loop thread) plus ``loop``
(retire, metrics, lock waits) over its decode steps. Median over the window's
200s. None where the program's spans lack the two phases."""

from benchmark import reduce


def read(obs):
    stalls = [1e3 * (s["phases"]["interleave"] + s["phases"]["loop"]) / s["steps"]
              for _, s in reduce.joined(obs)
              if s.get("steps") and "interleave" in s["phases"] and "loop" in s["phases"]]
    return reduce.median(stalls) if stalls else None
