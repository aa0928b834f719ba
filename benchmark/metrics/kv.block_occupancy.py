"""Mean share of the paged arena's blocks in use, ``block_stats()`` sampled
each second of the window."""

def read(obs):
    occ = (obs.get("serve") or {}).get("window", {}).get("block_occupancy")
    return 100.0 * sum(occ) / len(occ) if occ else None
