"""ISSUE 38: decode one step ahead. The pool's ``step()`` is ``dispatch()``
(the launch: a slot's next token stays on the device, where the next step
reads it) then ``collect()`` (the one fetch a step); the serving loop
dispatches step n+1 BEFORE it collects and retires step n, so the host's work
a token runs under the device's. What is pinned here: a pool driven one step
ahead under churn is the pool driven step by step, in every family; a token in
flight for a released slot is dropped; and the loop's order of calls, its
evictions and its account of a request's life."""

import dataclasses
import importlib.util
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.models import transformer as tfm
from deeplearning4j_tpu.models import trinity as tr
from deeplearning4j_tpu.models.paged_decode import (KvCacheLostError,
                                                    PagedDecodeSlotPool)
from deeplearning4j_tpu.monitoring import MetricsRegistry, flight
from deeplearning4j_tpu.serving import (DeadlineExceededError,
                                        GenerativeInferenceExecutor,
                                        JsonModelServer)

from test_cache_groups import _keye, _kimi, _transformer  # (cfg, params, stats keys)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCK_T, MAX_LEN, SLOTS, WINDOW = 8, 64, 3, 16


def _trinity():
    """Two cache groups: one full layer, four behind a window of 16."""
    cfg = tr.TrinityConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=5, num_dense_layers=1,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        intermediate_size=64, moe_intermediate_size=16, num_experts=8,
        n_resident_experts=8, num_experts_per_tok=2, sliding_window=WINDOW,
        max_position_embeddings=MAX_LEN, param_dtype=jnp.float32, attn_impl="xla",
        moe_tile=8)
    return cfg, tr.init_params(jax.random.key(0), cfg), None


FAMILIES = {"transformer": _transformer, "kimi_k2": _kimi, "keye_vl": _keye,
            "trinity": _trinity}


@pytest.fixture(scope="module", params=list(FAMILIES))
def family(request):
    return FAMILIES[request.param]()


def _pool(family, **kw):
    cfg, params, _ = family
    return PagedDecodeSlotPool(params, cfg, slots=SLOTS, block_T=BLOCK_T,
                               max_len=MAX_LEN, **kw)


def prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 60, n).astype(np.int32)


# admissions before the step of a turn: (request, prompt, budget). A's 13
# positions grow to 26: over the block edges at 16 and 24 and, in a windowed
# group, past the window's edge (its block 0 is handed back at position 23).
# B has A's prompt: where prefixes are shared it joins A's tail block, which A
# has written into since, and copies it before its first write, with a step in
# flight. B and C end on different steps; D takes a slot one of them left.
CHURN = {0: [("A", prompt(13), 14)],
         2: [("B", prompt(13), 4), ("C", prompt(20, 1), 3)],
         7: [("D", prompt(30, 2), 6)]}
TURNS, SNAPSHOTS = 13, (2, 8)


def _drive(pool, ahead):
    """Run ``CHURN``: a step a turn through ``step()`` or, one step ahead,
    ``dispatch()`` with the step before it collected AFTER it. A request is
    released when its budget is collected. Returns the tokens a request and,
    at the snapshots (with nothing in flight), every live slot's tables and
    cached rows."""
    slot_of, budget, tokens, seen = {}, {}, {}, {}

    def credit(out):
        for slot, toks in (out or {}).items():
            name = next(n for n, s in slot_of.items() if s == slot)
            tokens[name].extend(toks)
            if len(tokens[name]) >= budget[name]:
                pool.release(slot_of.pop(name))

    for turn in range(TURNS):
        for name, p, b in CHURN.get(turn, ()):
            slot_of[name], first = pool.admit(p, b)
            budget[name], tokens[name] = b, [first]
        if ahead:
            pool.dispatch()
            if len(pool._flying) > 1:
                credit(pool.collect())
        else:
            credit(pool.step())
        if turn in SNAPSHOTS:
            while pool._flying:
                credit(pool.collect())
            seen[turn] = {
                name: ([t.tolist() for t in pool.block_tables(slot)],
                       [np.asarray(a) for a in pool.cached_rows(
                           slot, int(pool._positions[slot]))])
                for name, slot in slot_of.items()}
    while pool._flying:
        credit(pool.collect())
    assert not slot_of  # every budget was served
    return tokens, seen


def test_a_pool_driven_one_step_ahead_is_the_pool_driven_step_by_step(family):
    plain, ahead = _pool(family), _pool(family)
    want, want_seen = _drive(plain, ahead=False)
    got, got_seen = _drive(ahead, ahead=True)
    assert got == want
    assert [len(want[n]) for n in "ABCD"] == [14, 4, 3, 6]
    for turn in SNAPSHOTS:
        assert set(got_seen[turn]) == set(want_seen[turn]) and want_seen[turn]
        for name, (tables, rows) in want_seen[turn].items():
            assert got_seen[turn][name][0] == tables, (turn, name)
            for a, b in zip(got_seen[turn][name][1], rows):
                np.testing.assert_array_equal(a, b)
    a, b = ahead.block_stats(), plain.block_stats()
    assert a.pop("kv_steps") == b.pop("kv_steps") == TURNS
    assert b.pop("kv_steps_overlapped") == 0
    assert a.pop("kv_steps_overlapped") >= TURNS - 1 - len(SNAPSHOTS)
    assert a == b and a["blocks_free"] == a["blocks_total"]
    assert plain.decode_traces == ahead.decode_traces == 1
    if ahead.family.shares_prefix:
        assert ahead._copy_fn._cache_size() == 1  # B copied A's tail block
    if ahead._windowed:
        assert a["kv_window_blocks_freed"] > 0


def test_a_token_in_flight_for_a_released_slot_is_dropped(family):
    """EOS or a deadline releases a slot whose next step is already running:
    that token belongs to nobody, and the slot's next tenant starts from its
    own prefill's token."""
    pool, twin = _pool(family), _pool(family)
    a, _ = pool.admit(prompt(9), 8)
    b, first_b = pool.admit(prompt(11, 1), 8)
    assert pool.dispatch() and pool.dispatch()
    pool.release(a)                       # two of a's tokens are in flight
    c, first_c = pool.admit(prompt(12, 2), 8)
    assert c == a
    outs = [pool.collect(), pool.collect()]
    assert [set(o) for o in outs] == [{b}, {b}]   # nothing for a or for c
    assert pool.dispatch()
    outs.append(pool.collect())
    assert set(outs[2]) == {b, c} and pool.collect() is None
    assert pool._emitted[c] == 2 and pool._emitted[b] == 4
    # b and c alone, step by step: b's three tokens, and c's first step
    tb, fb = twin.admit(prompt(11, 1), 8)
    tc, fc = twin.admit(prompt(12, 2), 8)
    assert (fb, fc) == (first_b, first_c)
    want = [twin.step() for _ in range(3)]
    assert [o[b] for o in outs] == [w[tb] for w in want]
    assert outs[2][c] == want[0][tc]


def test_a_failed_fetch_loses_the_cache_once(family):
    class Lost:
        def __array__(self, *a, **kw):
            raise RuntimeError("injected: the device dropped the result")

    pool = _pool(family)
    pool.admit(prompt(9), 8)
    assert pool.dispatch() and pool.dispatch()
    pool._flying[0].results = (Lost(),)
    with pytest.raises(KvCacheLostError, match="cache reset"):
        pool.collect()
    # reset: the step behind the failed one went with it, every slot is free
    assert pool.collect() is None and pool.free_slots == SLOTS
    assert pool.block_stats()["blocks_free"] == pool.total_blocks
    slot, first = pool.admit(prompt(9), 3)
    assert pool.step()[slot] and pool.decode_traces == 1


def test_a_pool_with_a_draft_leaves_nothing_running():
    """Its next positions wait for the accepted count: ``dispatch()`` reads
    the step back itself, ``collect()`` hands the answer over."""
    cfg, params, _ = _transformer()
    draft_cfg = dataclasses.replace(cfg, n_layers=1)
    draft = tfm.init_params(jax.random.key(9), draft_cfg)
    kw = dict(slots=SLOTS, block_T=BLOCK_T, draft_params=draft,
              draft_cfg=draft_cfg, spec_tokens=2)
    pool = PagedDecodeSlotPool(params, cfg, **kw)
    twin = PagedDecodeSlotPool(params, cfg, **kw)
    slot, _ = pool.admit(prompt(9), 9)
    twin.admit(prompt(9), 9)
    for _ in range(3):
        assert pool.dispatch() is False
        assert all(f.answer is not None for f in pool._flying)
        assert pool.collect() == twin.step()
    stats = pool.block_stats()
    assert stats["kv_steps"] == 3 and stats["kv_steps_overlapped"] == 0
    assert pool.collect() is None and slot == 0


# -- the serving loop --------------------------------------------------------


class AheadSession:
    """A slot pool stand-in that decodes one step ahead, as the paged pool
    does (or, ``ahead=False``, reads every step back at its dispatch, as a
    pool with a draft does): a sequence emits ``prompt[-1] + 1, + 2, ...``,
    ``log`` keeps the order of the loop's calls."""

    max_len = None

    def __init__(self, slots=2, ahead=True, step_s=0.0, eos_id=None):
        self.slots, self.ahead, self.step_s, self.eos_id = slots, ahead, step_s, eos_id
        self._next, self._left, self._flying = {}, {}, []
        self.log, self.n = [], 0

    @property
    def free_slots(self):
        return self.slots - len(self._next)

    def admit(self, prompt, max_new_tokens):
        slot = min(set(range(self.slots)) - set(self._next))
        first = int(np.asarray(prompt)[-1]) + 1
        self._next[slot], self._left[slot] = first + 1, max_new_tokens - 1
        self.log.append(("admit", slot, len(self._flying)))
        return slot, first

    def dispatch(self):
        riders = {s: [t] for s, t in self._next.items() if self._left[s] > 0}
        if not riders:
            return False
        for s in riders:
            self._next[s] += 1
            self._left[s] -= 1
        self.n += 1
        self._flying.append((self.n, riders))
        self.log.append(("dispatch", self.n))
        return self.ahead

    def collect(self):
        if not self._flying:
            return None
        time.sleep(self.step_s)
        n, riders = self._flying.pop(0)
        self.log.append(("collect", n))
        return riders

    def step(self):
        self.dispatch()
        return self.collect() or {}

    def release(self, slot):
        del self._next[slot], self._left[slot]
        for _, riders in self._flying:
            riders.pop(slot, None)
        self.log.append(("release", slot))


def _run(session, requests, **kw):
    """Submit ``requests`` ((prompt, budget, deadline_ms, pause after)) to an
    executor over ``session``; return their futures, finished."""
    ex = GenerativeInferenceExecutor(session, registry=MetricsRegistry(), **kw).start()
    try:
        futs = []
        for p, budget, deadline_ms, pause in requests:
            futs.append(ex.submit(p, max_new_tokens=budget, deadline_ms=deadline_ms))
            time.sleep(pause)
        for f in futs:
            assert f.wait(20.0)
        return futs, ex.stats()
    finally:
        ex.stop(drain=True)


def test_the_loop_dispatches_the_next_step_before_it_collects_the_last():
    session = AheadSession(step_s=0.002)
    (fut,), stats = _run(session, [([5], 8, None, 0.0)])
    np.testing.assert_array_equal(fut.result, np.arange(6, 14))
    order = [e[:2] for e in session.log if e[0] in ("dispatch", "collect")]
    at = {e: i for i, e in enumerate(order)}
    assert [n for kind, n in order if kind == "dispatch"] == list(range(1, 8))
    for n in range(1, 7):   # step n+1 is on the device while step n is retired
        assert at[("dispatch", n + 1)] < at[("collect", n)]
    # the last token finishes the request at ITS collect: nothing after it
    assert order[-1] == ("collect", 7) and stats["steps"] == 7


def test_an_admission_meets_no_step_in_flight():
    """With a candidate queued, the step in flight is collected and retired
    FIRST: no finished request waits out another's prefill, and ``admit``
    never races a step's host state."""
    session = AheadSession(slots=3, step_s=0.003)
    futs, _ = _run(session, [([1], 60, None, 0.03), ([20], 30, None, 0.03),
                             ([40], 5, None, 0.0)])
    assert [len(f.result) for f in futs] == [60, 30, 5]
    for f, first in zip(futs, (2, 21, 41)):
        np.testing.assert_array_equal(f.result, first + np.arange(len(f.result)))
    admits = [e for e in session.log if e[0] == "admit"]
    assert len(admits) == 3 and all(in_flight == 0 for _, _, in_flight in admits)
    # and between admissions the loop did run ahead
    order = [e[:2] for e in session.log if e[0] in ("dispatch", "collect")]
    assert sum(1 for a, b in zip(order, order[1:])
               if a[0] == b[0] == "dispatch") >= 3


@pytest.mark.parametrize("ahead", [True, False], ids=["ahead", "at_dispatch"])
def test_eos_and_a_deadline_evict_as_they_did(ahead):
    """EOS ends a request at the token's collect (the step dispatched after
    it is dropped by the session, and the slot's next tenant gets none of
    it); a deadline evicts mid-decode and frees the slot."""
    session = AheadSession(slots=1, ahead=ahead, step_s=0.004, eos_id=9)
    (eos, doomed, after), stats = _run(session, [
        ([4], 30, None, 0.0), ([100], 10_000, 80, 0.0), ([50], 4, None, 0.0)])
    np.testing.assert_array_equal(eos.result, [5, 6, 7, 8, 9])   # EOS inclusive
    assert isinstance(doomed.error, DeadlineExceededError)
    assert 1 < len(doomed.tokens) < 10_000
    np.testing.assert_array_equal(doomed.tokens, 101 + np.arange(len(doomed.tokens)))
    np.testing.assert_array_equal(after.result, [51, 52, 53, 54])
    assert stats["evicted"] == 1 and session.free_slots == 1
    assert not session._flying  # nothing is left running


def test_request_span_phases_still_tile_a_request_decoded_ahead():
    rec = flight.FlightRecorder(proc="ahead-test", capacity=4096)
    flight.set_flight_recorder(rec)
    session = AheadSession(slots=2, step_s=0.004)
    server = JsonModelServer(None, generative_session=session,
                             default_max_new_tokens=4,
                             registry=MetricsRegistry()).start()

    def post(rid, budget):
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/predict", data=json.dumps([3]).encode(),
            headers={"Content-Type": "application/json", "X-Request-Id": rid,
                     "X-Max-New-Tokens": str(budget)})
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert len(json.loads(resp.read())["output"]) == budget

    try:
        assert server.wait_ready(60.0)
        threads = [threading.Thread(target=post, args=(f"r{i}", n))
                   for i, n in enumerate([9, 4, 6, 3])]
        for th in threads:
            th.start()
            time.sleep(0.01)
        for th in threads:
            th.join(60.0)
            assert not th.is_alive()
        steps = server._executor.stats()["steps"]
    finally:
        server.stop()
        flight.set_flight_recorder(None)
    spans = [e for e in rec.events() if e["kind"] == "request_span"]
    assert len(spans) == 4
    for ev in spans:
        p = ev["phases"]
        assert list(p) == ["read", "parse", "queue", "prefill", "decode",
                           "interleave", "loop", "handoff", "serialize", "write"]
        assert all(v >= 0.0 for v in p.values()), p
        assert sum(p.values()) == pytest.approx(ev["t_end"] - ev["t_start"], abs=1e-6)
        # a step's period: collect to collect, every one of them its own
        assert ev["steps"] == len(ev["step_ms"]) == ev["last_step"] - ev["first_step"] + 1
        assert p["decode"] == pytest.approx(sum(ev["step_ms"]) / 1e3,
                                            abs=1e-5 * ev["steps"])
        assert p["decode"] >= ev["steps"] * 0.004
    assert max(ev["last_step"] for ev in spans) == steps


# -- the reader ----------------------------------------------------------------


@pytest.mark.parametrize("blocks,want", [
    ({"kv_steps": 200, "kv_steps_overlapped": 190}, 95.0),
    ({"kv_steps": 8, "kv_steps_overlapped": 0}, 0.0),
    ({"kv_steps": 0, "kv_steps_overlapped": 0}, None),     # no step: no share
    ({"kv_blocks_read": 3}, None),                         # the parent counts neither
    (None, None),
], ids=["present", "none_overlapped", "zero_steps", "absent", "no_blocks"])
def test_step_overlap_share_on_a_hand_made_observation(blocks, want):
    spec = importlib.util.spec_from_file_location(
        "metric_kv_step_overlap_share",
        os.path.join(ROOT, "benchmark", "metrics", "kv.step_overlap_share.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    stats = {} if blocks is None else {"blocks": blocks}
    got = mod.read({"serve": {"executor_stats": stats}})
    assert got == want if want is None else got == pytest.approx(want)
    assert mod.read({"serve": None}) is None
