"""Share of the mapped KV blocks that decode attention was asked to visit:
``kv_blocks_read`` over ``kv_blocks_mapped`` of the pool's ``block_stats()``
(cumulative, as ``/stats`` gave them after the window). Read counts, over
live slots, the blocks up to a step's last position; mapped counts ``slots x
max_blocks`` a step, what a dense gather through the tables visits. None
where the program does not count them."""


def read(obs):
    blocks = ((obs.get("serve") or {}).get("executor_stats") or {}).get("blocks") or {}
    read_, mapped = blocks.get("kv_blocks_read"), blocks.get("kv_blocks_mapped")
    return 100.0 * read_ / mapped if read_ is not None and mapped else None
