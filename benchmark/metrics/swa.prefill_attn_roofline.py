"""Roofline share of the windowed flash forward (the sliding layers' prefill):
the visible (query, key) pairs of a prompt, ``4 x 128`` FLOPs a pair a query
head (``work_trinity.prefill_attn_work``), over the traced time of the Mosaic
calls named ``flash_fwd_swa``. A call's trace name carries its bucket; what a
bucket's call is REQUIRED to do is the mean over the window's prompts of that
bucket (the bucket's padding is no work), or the bucket's own length where
the window sent none. None where the trace has no such call."""

from benchmark import work, work_trinity


def read(obs):
    tr, peaks, fam = obs.get("trace"), obs.get("peaks"), obs.get("family")
    if not tr or not peaks or not fam or "window" not in fam.get("shapes", {}):
        return None
    m = fam["shapes"]
    records = ((obs.get("serve") or {}).get("window") or {}).get("records") or []
    by_bucket = {}
    for rec in records:
        by_bucket.setdefault(work_trinity.bucket_of(rec["prompt"], m["block_T"]),
                             []).append(rec["prompt"])
    least = spent = 0.0
    for name, agg in tr["mosaic_calls"].items():
        bucket = work_trinity.call_bucket(name)
        if work_trinity.WINDOWED_PREFILL_KERNEL not in name or not bucket:
            continue
        prompts = by_bucket.get(bucket) or [bucket]
        need = [work.least_seconds(*work_trinity.prefill_attn_work(
            m, n=n, window=m["window"]), peaks) for n in prompts]
        least += agg["calls"] * sum(need) / len(need)
        spent += agg["seconds"]
    return 100.0 * least / spent if spent else None
