"""Share of decode steps dispatched while the step before them was still
uncollected: ``kv_steps_overlapped`` over ``kv_steps`` of the pool's
``block_stats()`` (cumulative, as ``/stats`` gave them after the window). Such
a step's host work (retirement of the step before, gauges, tables, uploads,
the launch) ran under the device's; the others began a run after an admission
or an idle loop. None where the program does not count them, or took no
step."""


def read(obs):
    b = ((obs.get("serve") or {}).get("executor_stats") or {}).get("blocks") or {}
    steps, overlapped = b.get("kv_steps"), b.get("kv_steps_overlapped")
    return 100.0 * overlapped / steps if steps and overlapped is not None else None
