"""Traffic from parameters: lengths, arrivals and token contents.

The SET of sizes and arrivals of a cell is fixed by its traffic file (drawn
once from ``traffic_seed``), so every ``--seed`` offers the same work; the
seed rotates the schedule (the same requests, another phase) and draws the
token contents and the weights. Arrivals are a Poisson process conditioned on
its expected count: ``round(rate * seconds)`` instants uniform over a period
as long as the window, repeated before it for the pre-roll, so the window
opens on a steady state and always holds the same requests.
"""

from __future__ import annotations

from typing import List, NamedTuple

import numpy as np


class Request(NamedTuple):
    index: int
    due_s: float       # relative to the window's opening; negative = pre-roll
    prompt_len: int
    answer_len: int
    in_window: bool
    prompt_id: int     # requests with one id send the same prompt (``repeat_each``)


def draw_lengths(rs: np.random.RandomState, spec: dict, n: int) -> np.ndarray:
    if spec["dist"] == "lognormal":
        x = rs.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif spec["dist"] == "uniform":
        x = rs.uniform(spec["min"], spec["max"], n)
    elif spec["dist"] == "fixed":
        x = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def schedule(traffic: dict, *, seed: int, seconds: float,
             rate_rps: float = None) -> List[Request]:
    """The open-loop schedule of one run: requests due in
    ``[-preroll_s, seconds)``, sorted by due time."""
    rate = float(rate_rps if rate_rps is not None else traffic["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    fixed = np.random.RandomState(int(traffic["traffic_seed"]) % (2 ** 32))
    if traffic.get("arrivals", "poisson") == "poisson":
        phase = np.sort(fixed.uniform(0.0, seconds, n))
    elif traffic["arrivals"] == "uniform":
        phase = (np.arange(n) + 0.5) * seconds / n
    else:
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    prompts = draw_lengths(fixed, traffic["prompt_tokens"], n)
    answers = draw_lengths(fixed, traffic["answer_tokens"], n)
    # ``repeat_each`` k: every prompt is asked k times (a document asked about
    # more than once); 1 = all prompts distinct
    asked = (np.arange(n) // max(1, int(traffic.get("repeat_each", 1))))
    prompts = prompts[np.searchsorted(asked, asked)]
    # the seed turns the period: same requests, same gaps, another phase
    offset = np.random.RandomState(seed % (2 ** 32)).uniform(0.0, seconds)
    due = (phase + offset) % seconds
    reqs = []
    for i in range(n):
        reqs.append((due[i], int(prompts[i]), int(answers[i]), True,
                     int(asked[i])))
        if due[i] - seconds >= -float(traffic["preroll_s"]):  # its pre-roll twin
            reqs.append((due[i] - seconds, int(prompts[i]), int(answers[i]),
                         False, int(asked[i]) + n))
    reqs.sort()
    return [Request(i, float(d), *rest) for i, (d, *rest) in enumerate(reqs)]


def prompt_tokens(rs: np.random.RandomState, length: int, vocab: int,
                  shared: np.ndarray = None) -> np.ndarray:
    toks = rs.randint(1, vocab, length).astype(np.int32)
    if shared is not None and len(shared):
        k = min(len(shared), length)
        toks[:k] = shared[:k]
    return toks


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))
