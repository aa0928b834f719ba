"""Share of a token's expert choices that landed on an expert held here:
``moe_resident_assignments`` over ``experts_per_token x moe_routed_tokens``
(cumulative, from ``/stats``). Even routing over 384 experts with 12 held
gives 3.1 %: it shows that the cut is the cut. None where the program counts
no experts."""


def read(obs):
    b = ((obs.get("serve") or {}).get("executor_stats") or {}).get("blocks") or {}
    k = ((obs.get("family") or {}).get("shapes") or {}).get("experts_per_token")
    landed, routed = b.get("moe_resident_assignments"), b.get("moe_routed_tokens")
    if landed is None or not routed or not k:
        return None
    return 100.0 * landed / (k * routed)
