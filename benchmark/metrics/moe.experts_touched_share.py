"""Share of the resident expert weights that a decode step reads:
``moe_experts_touched`` over ``moe_experts_resident`` of the pool's
``block_stats()`` (cumulative, as ``/stats`` gave them after the window).
None where the program counts no experts."""


def read(obs):
    b = ((obs.get("serve") or {}).get("executor_stats") or {}).get("blocks") or {}
    touched, resident = b.get("moe_experts_touched"), b.get("moe_experts_resident")
    return 100.0 * touched / resident if touched is not None and resident else None
