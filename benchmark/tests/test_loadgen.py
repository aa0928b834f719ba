"""The traffic generator: every seed offers the same work."""

import numpy as np
import pytest

from benchmark import loadgen

CHAT = {"prompt_tokens": {"dist": "lognormal", "median": 96, "sigma": 0.8, "min": 8, "max": 512},
        "answer_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 12, "max": 96},
        "arrivals": "poisson", "rate_rps": 3.0, "traffic_seed": 1, "preroll_s": 8.0}


def in_window(reqs):
    return sorted((r.prompt_len, r.answer_len) for r in reqs if r.in_window)


def test_every_seed_offers_the_same_requests():
    a = loadgen.schedule(CHAT, seed=1, seconds=40.0)
    b = loadgen.schedule(CHAT, seed=3_000_000_019, seconds=40.0)
    assert in_window(a) == in_window(b)
    assert len(in_window(a)) == 120
    assert [r.due_s for r in a] != [r.due_s for r in b]


def test_same_seed_same_schedule_and_sorted():
    a = loadgen.schedule(CHAT, seed=5, seconds=40.0)
    assert a == loadgen.schedule(CHAT, seed=5, seconds=40.0)
    due = [r.due_s for r in a]
    assert due == sorted(due) and due[0] >= -8.0 and due[-1] < 40.0


def test_preroll_repeats_the_tail_of_the_period():
    a = loadgen.schedule(CHAT, seed=5, seconds=40.0)
    pre = [r for r in a if not r.in_window]
    win = {round(r.due_s, 9): r for r in a if r.in_window}
    assert pre and all(r.due_s < 0 for r in pre)
    for r in pre:
        twin = win[round(r.due_s + 40.0, 9)]
        assert (twin.prompt_len, twin.answer_len) == (r.prompt_len, r.answer_len)


def test_lengths_respect_their_clips():
    rs = np.random.RandomState(0)
    x = loadgen.draw_lengths(rs, CHAT["prompt_tokens"], 5000)
    assert x.min() >= 8 and x.max() <= 512
    assert 85 <= np.median(x) <= 107


def test_rate_override_changes_the_count():
    assert len(in_window(loadgen.schedule(CHAT, seed=1, seconds=10.0, rate_rps=5.0))) == 50


def test_unknown_distribution_is_an_error():
    with pytest.raises(ValueError):
        loadgen.draw_lengths(np.random.RandomState(0), {"dist": "zipf", "min": 1, "max": 2}, 3)


def test_repeat_each_asks_every_prompt_k_times():
    reqs = loadgen.schedule({**CHAT, "repeat_each": 3}, seed=2, seconds=40.0)
    win = [r for r in reqs if r.in_window]
    groups = {}
    for r in win:
        groups.setdefault(r.prompt_id, set()).add(r.prompt_len)
    assert len(groups) == 40 and all(len(v) == 1 for v in groups.values())
    assert not {r.prompt_id for r in win} & {r.prompt_id for r in reqs if not r.in_window}


def test_prompts_are_distinct_by_default():
    reqs = loadgen.schedule(CHAT, seed=2, seconds=40.0)
    assert len({r.prompt_id for r in reqs}) == len(reqs)
