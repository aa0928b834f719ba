"""Share of the cached rows of live slots whose K and V a decode step's
attention read: ``dsa_selected_rows`` over ``dsa_live_rows`` of the pool's
``block_stats()`` (cumulative over layers and steps, as ``/stats`` gave them
after the window). The indexer scores every live row; attention reads the
selection only, so with contexts of 4k-16k and 2,048 selected this is 12-50 %.
None where the program does not count them."""


def read(obs):
    b = ((obs.get("serve") or {}).get("executor_stats") or {}).get("blocks") or {}
    picked, live = b.get("dsa_selected_rows"), b.get("dsa_live_rows")
    return 100.0 * picked / live if picked is not None and live else None
