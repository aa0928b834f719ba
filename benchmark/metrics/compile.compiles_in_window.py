"""Programs compiled or loaded from the cache INSIDE the measured window
(XLA compiles + cache hits, ``RecompileWatchdog`` and ``compilecache``): 0."""

def read(obs):
    return obs["counters"]["compiles_in_window"]
