"""The busiest resident expert's tokens over the mean resident expert's, a
layer a step: ``moe_load_max`` over ``moe_load_sum / resident experts``
(both summed over layers and steps, from ``/stats``). 1 is even; the busiest
expert's rows are what a step's expert loop runs longest on. None where the
program counts no experts."""


def read(obs):
    b = ((obs.get("serve") or {}).get("executor_stats") or {}).get("blocks") or {}
    n = ((obs.get("family") or {}).get("shapes") or {}).get("resident_experts")
    top, total = b.get("moe_load_max"), b.get("moe_load_sum")
    return top * n / total if top is not None and total and n else None
