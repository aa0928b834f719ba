"""What one token stores in the paged cache over all layers and arenas:
``kv_cache_bytes_per_token`` of the pool's ``block_stats()``, from
``/stats``. None where the program does not say."""


def read(obs):
    b = ((obs.get("serve") or {}).get("executor_stats") or {}).get("blocks") or {}
    return b.get("kv_cache_bytes_per_token")
