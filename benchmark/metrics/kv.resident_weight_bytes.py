"""Bytes of the weights the pool's decode program is handed:
``resident_weight_bytes`` of the pool's ``block_stats()``, from ``/stats``
after the window: every distinct leaf of the resident tree (a draft's too).
Where the pool serves from the caller's float32 masters it would read their
size; from weights cast once, the compute-dtype copy plus what stays float32.
None where the program does not count it."""


def read(obs):
    b = ((obs.get("serve") or {}).get("executor_stats") or {}).get("blocks") or {}
    return b.get("resident_weight_bytes")
