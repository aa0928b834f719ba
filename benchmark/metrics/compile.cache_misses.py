"""Programs this run had to compile because the persistent cache did not hold
them (``monitoring/compilecache``): 0 in every run after a cell's first."""

def read(obs):
    return obs["counters"]["cache_misses"]
