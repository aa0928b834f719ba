"""Prefill time a thousand prompt tokens: the median over the window's
requests of the request span's ``prefill`` phase over the prompt's length in
thousands of tokens. Only a cell whose pool keeps a windowed cache group (the
``trinity`` family: ``swa_rows_read`` in ``/stats``) reports it; None
elsewhere."""

from benchmark import reduce


def read(obs):
    b = ((obs.get("serve") or {}).get("executor_stats") or {}).get("blocks") or {}
    rows = [(rec, s) for rec, s in reduce.joined(obs) if "prefill" in s.get("phases", {})]
    if "swa_rows_read" not in b or not rows:
        return None
    return reduce.median([1e3 * s["phases"]["prefill"] / (rec["prompt"] / 1e3)
                          for rec, s in rows])
