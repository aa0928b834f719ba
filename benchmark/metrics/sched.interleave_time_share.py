"""Of the seconds the window's requests held a slot, the share in which
they stood still for another request's prefill: ``interleave`` over
``decode + interleave + loop``, summed over the window's 200s. One thread
admits and decodes, so this is decode capacity spent waiting on prefill.
None where the program's spans lack the phases."""

from benchmark import reduce


def read(obs):
    rows = [s["phases"] for _, s in reduce.joined(obs)
            if "interleave" in s["phases"] and "loop" in s["phases"]]
    held = sum(p["decode"] + p["interleave"] + p["loop"] for p in rows)
    return 100.0 * sum(p["interleave"] for p in rows) / held if held else None
