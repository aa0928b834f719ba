"""Share of the cached rows of live slots that a decode step's attention
reads, summed over layers: ``swa_rows_read`` (a full layer every row, a
sliding layer the window's) over ``swa_rows_windowless`` (every layer every
row) of the pool's ``block_stats()`` (cumulative over steps, as ``/stats``
gave them after the window). Lower is better: it is what the window saves a
step in cache traffic, and with three sliding layers to one full it cannot
fall under a share of full layers of all. None where the program keeps no
windowed cache group."""


def read(obs):
    b = ((obs.get("serve") or {}).get("executor_stats") or {}).get("blocks") or {}
    read_, all_ = b.get("swa_rows_read"), b.get("swa_rows_windowless")
    return 100.0 * read_ / all_ if read_ is not None and all_ else None
