"""ISSUE 39: the serving loop's step account.

The loop thread's spans feed ONE ``StepPhaseRecorder`` (a ring of rows, a
collected step each): a row's exclusive phases and ``other`` add up to its
period exactly, rows fall into segments by whether a profiler session is
listening, the ring forgets rows but not counts, the pool says whether a
step's result was ready before the host read it, and a thread that feeds no
recorder reaches none. No assertion here reads a wall clock: durations come
from a clock that ticks once a reading.
"""

import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.monitoring import MetricsRegistry, trace
from deeplearning4j_tpu.monitoring.trace import (LOOP_SPANS, SEGMENTS,
                                                 StepPhaseRecorder,
                                                 feed_spans_to, span)
from deeplearning4j_tpu.serving import executor as executor_mod
from deeplearning4j_tpu.serving.executor import (GenerationFuture,
                                                 GenerativeInferenceExecutor)
from test_decode_ahead import AheadSession
from test_serving_spans import SleepySession, _profile, _serve

TICK = 10  # ns the fake clock advances a reading


class TickingTime:
    """``time`` for a module under test: ``perf_counter_ns`` advances
    ``TICK`` ns a reading, whatever the host is doing; the rest is the real
    module's."""

    def __init__(self):
        self.now = 0

    def perf_counter_ns(self):
        self.now += TICK
        return self.now

    def __getattr__(self, name):
        return getattr(time, name)


@pytest.fixture
def ticking(monkeypatch):
    clock = TickingTime()
    monkeypatch.setattr(trace, "time", clock)
    monkeypatch.setattr(executor_mod, "time", clock)
    yield clock
    feed_spans_to(None)


class FetchingSession(AheadSession):
    """An :class:`AheadSession` whose ``collect`` opens the pool's spans:
    ``kv.step.fetch`` (saying ``ready`` as told) with a span nested in it,
    then ``kv.step.land``; its ``admit`` opens ``kv.prefill``."""

    def __init__(self, ready=None, **kw):
        super().__init__(**kw)
        self.ready = ready

    def admit(self, prompt, max_new_tokens):
        with span("kv.prefill", bucket=8):
            return super().admit(prompt, max_new_tokens)

    def collect(self):
        if not self._flying:
            return None
        stats = {} if self.ready is None else {"ready": int(self.ready)}
        with span("kv.step.fetch", **stats):
            with span("kv.prefill.fetch"):   # any declared name, nested
                pass
        with span("kv.step.land"):
            return super().collect()


def _rows(ex):
    snap = ex._account.snapshot()
    col = {name: i for i, name in enumerate(snap["columns"])}
    return snap, col, snap["rows"]


def _admit(ex, active, prompt, budget):
    fut = GenerationFuture(np.asarray(prompt, np.int32), None, budget)
    with span("sched.admit", request_id="r", prompt_len=len(prompt)):
        ex._admit_into_slot(fut, active)
    return fut


def _drive(session, script):
    """The loop's own calls, on this thread and in a fixed order: ``script``
    is a list of ("admit", prompt, budget) | ("turn", launch)."""
    ex = GenerativeInferenceExecutor(session, registry=MetricsRegistry())
    feed_spans_to(ex._account)
    active, futs = {}, []
    for op, *args in script:
        if op == "admit":
            futs.append(_admit(ex, active, *args))
        else:
            ex._decode_step(active, launch=args[0] and bool(active))
    feed_spans_to(None)
    return ex, futs


# -- the row's identity ------------------------------------------------------


def test_a_rows_exclusive_phases_and_other_add_up_to_its_period(ticking):
    """Plain turns, spans nested in spans, and a run that an admission
    interrupts: in every row the phase columns and ``other`` sum to the
    period, to the nanosecond; a span nested in another counts only toward
    itself; what ran between two runs of steps is in no row."""
    script = [("admit", [5], 6), ("turn", True), ("turn", True), ("turn", True),
              # an admission waits: the step in flight is collected first
              ("turn", False), ("admit", [40], 3), ("turn", True), ("turn", True),
              ("turn", True), ("turn", True), ("turn", True)]
    ex, futs = _drive(FetchingSession(ready=True, slots=2), script)
    assert [len(f.tokens) for f in futs] == [6, 3] and all(f.done for f in futs)
    snap, col, rows = _rows(ex)
    assert len(rows) == ex.stats()["steps"] == 5
    phases = [col[name] for name in LOOP_SPANS]
    for r in rows:
        assert r[phases].sum() + r[col["other"]] == r[col["period"]]
        assert r[col["other"]] >= 0
        # fetch is start, (child start, child end), end: 3 ticks, 1 the child's
        assert r[col["kv.step.fetch"]] == 2 * TICK
        assert r[col["kv.prefill.fetch"]] == TICK
        assert r[col["kv.step.land"]] == TICK
        # admission and prefill lie outside every period
        assert r[col["sched.admit"]] == r[col["kv.prefill"]] == 0
    assert list(rows[:, col["step"]]) == [1, 2, 3, 4, 5]
    # the first step of a run rode under none; the others under the one before
    assert list(rows[:, col["overlapped"]]) == [0, 1, 1, 0, 1]
    outside = snap["outside_s"][0]
    assert outside["kv.prefill"] == pytest.approx(2 * TICK / 1e9)
    assert outside["sched.admit"] > 0 and outside["sched.retire"] > 0
    # and the whole of the thread's span time is on one page: rows + outside
    total = rows[:, phases].sum() / 1e9 + sum(outside.values())
    assert total > 0 and set(outside) <= set(LOOP_SPANS)


def test_exclusive_time_under_nesting_is_exact(ticking):
    rec = StepPhaseRecorder(ring=4, columns=("a", "b", "c"), fields=("n",))
    feed_spans_to(rec)
    with span("a"):            # 7 readings apart: 6 ticks
        with span("b"):        # 5 ticks
            with span("c"):    # 1 tick
                pass
            with span("c"):    # 1 tick
                pass
        pass
    rec.step_done(100, (7,))
    snap = rec.snapshot()
    assert snap["columns"] == ("n", "segment", "period", "a", "b", "c", "other")
    assert list(snap["rows"][0]) == [7, 0, 100, 2 * TICK, 3 * TICK, 2 * TICK,
                                     100 - 7 * TICK]


def test_the_loop_threads_account_over_a_sleepy_session():
    """Through the door, over ``SleepySession`` (a step read back at its
    dispatch, no span of its own): every step has its row, none was
    overlapped, a period runs from the dispatch to the collect and is all
    ``other``; retirement and the gauges lie between two periods."""
    session = SleepySession(slots=3, admit_s=0.0, step_s=0.0)
    rec = StepPhaseRecorder(ring=8, columns=LOOP_SPANS)  # this thread's own
    feed_spans_to(rec)
    try:
        spans, stats = _serve(session, budgets=[5, 3, 4], gap_s=0.0)
    finally:
        feed_spans_to(None)
    account = stats["step_account"]
    assert list(account) == ["untraced"]
    seg = account["untraced"]
    assert seg["steps"] == seg["rows"] == stats["steps"] >= 4
    assert seg["overlapped_share"] == 0.0 and seg["ready_share"] is None
    assert seg["phases_ms"] == {} and seg["other_ms"] == seg["period_ms"]
    assert set(seg["period_ms"]) == {"p50", "p90"}
    assert {"sched.idle", "sched.admit", "sched.decode_step", "sched.retire",
            "sched.gauges"} == set(seg["loop_s"])
    # a handler thread's spans (door.*) reached no account: not the loop's...
    assert not [n for n in list(seg["phases_ms"]) + list(seg["loop_s"])
                if n.startswith("door.")]
    # ...and not the one this thread fed while the server ran
    assert rec._acc == {} and rec.snapshot()["outside_s"] == [{}, {}, {}]


def test_a_thread_that_feeds_no_recorder_reaches_none(ticking):
    rec = StepPhaseRecorder(ring=4, columns=("door.read",))
    feed_spans_to(rec)   # THIS thread's

    def handler():
        with span("door.read"):
            pass

    th = threading.Thread(target=handler)
    th.start()
    th.join()
    assert rec.pending_ns("door.read") is None
    with span("door.read"):
        pass
    assert rec.pending_ns("door.read") == TICK


# -- segments, and a ring that wraps -------------------------------------------


class FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: ``is_enabled`` is what
    the test says."""

    listening = False

    def __init__(self, name, **stats):
        pass

    @staticmethod
    def is_enabled():
        return FakeAnnotation.listening

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_rows_fall_into_segments_by_the_profilers_flag(monkeypatch):
    monkeypatch.setattr(trace, "_annotation_types", (FakeAnnotation, FakeAnnotation))
    monkeypatch.setattr(FakeAnnotation, "listening", False)
    session = AheadSession(slots=1)
    ex = GenerativeInferenceExecutor(session, registry=MetricsRegistry()).start()
    try:
        def generate(n):
            fut = ex.submit([3], max_new_tokens=n + 1)
            assert fut.wait(20.0) and len(fut.result) == n + 1

        generate(4)
        FakeAnnotation.listening = True
        generate(6)
        generate(2)
        FakeAnnotation.listening = False
        generate(5)
        FakeAnnotation.listening = True     # a second session
        generate(3)
        FakeAnnotation.listening = False
        generate(1)
        stats = ex.stats()
    finally:
        ex.stop(drain=True)
    account = stats["step_account"]
    assert list(account) == list(SEGMENTS)
    assert [account[s]["steps"] for s in SEGMENTS] == [4, 6 + 2 + 3, 5 + 1]
    assert sum(account[s]["rows"] for s in SEGMENTS) == stats["steps"] == 21
    # admissions and idle waits, a segment each (the first request may find
    # the loop not yet idle)
    assert all(seg["loop_s"]["sched.admit"] > 0 for seg in account.values())
    assert all(account[s]["loop_s"]["sched.idle"] > 0 for s in SEGMENTS[1:])


def test_the_ring_wraps_without_losing_the_segment_counts():
    rec = StepPhaseRecorder(ring=8, columns=("a",), fields=("step",))
    for step in range(1, 21):
        rec.profiler_seen(6 <= step < 13)
        rec.add("a", step * 1e-9)
        rec.step_done(1000 + step, (step,))
    snap = rec.snapshot()
    assert snap["steps"] == [5, 7, 8] and len(snap["rows"]) == 8
    rows = snap["rows"][np.argsort(snap["rows"][:, 0])]
    assert list(rows[:, 0]) == list(range(13, 21))     # the newest eight
    assert list(rows[:, 1]) == [2] * 8                 # untraced_after_trace
    assert list(rows[:, 2] - rows[:, 3] - rows[:, 4]) == [0] * 8  # the identity
    assert list(rows[:, 3]) == list(range(13, 21))


# -- ready at collect -------------------------------------------------------------


class Result:
    """A step's result on the device, ready or not as told."""

    def __init__(self, value, ready):
        self.value, self.ready = value, ready

    def is_ready(self):
        return self.ready

    def __array__(self, *a, **kw):
        return np.asarray(self.value)


@pytest.mark.parametrize("ready", [True, False], ids=["ready", "still_running"])
def test_the_pool_says_whether_the_result_was_there_before_the_read(ready):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import transformer as tfm
    from deeplearning4j_tpu.models.paged_decode import PagedDecodeSlotPool

    cfg = tfm.TransformerConfig(vocab_size=61, d_model=16, n_heads=2,
                                n_layers=1, d_ff=32, max_len=32, causal=True,
                                dropout=0.0, compute_dtype=jnp.float32,
                                attn_impl="xla")
    pool = PagedDecodeSlotPool(tfm.init_params(jax.random.key(0), cfg), cfg,
                               slots=2, block_T=8)
    slot, _ = pool.admit([1, 2, 3], 4)
    rec = StepPhaseRecorder(ring=4, columns=LOOP_SPANS)
    feed_spans_to(rec)
    try:
        assert pool.dispatch()
        flight = pool._flying[0]
        flight.results = tuple(Result(np.asarray(r), ready) for r in flight.results)
        out = pool.collect()
    finally:
        feed_spans_to(None)
    assert len(out[slot]) == 1
    assert rec.last_stats["kv.step.fetch"] == {"ready": int(ready)}
    for name in ("kv.step.prepare", "kv.step.upload", "kv.step.dispatch",
                 "kv.step.fetch", "kv.step.land"):
        assert rec.pending_ns(name) is not None, name
    # a real result of a finished step is ready
    assert pool.dispatch()
    jax.block_until_ready(pool._flying[0].results)
    assert pool._flying[0].results[0].is_ready()
    pool.collect()


@pytest.mark.parametrize("ready,share", [(True, 1.0), (False, 0.0), (None, None)],
                         ids=["ready", "still_running", "not_said"])
def test_ready_at_collect_reaches_the_row_and_the_share(ticking, ready, share):
    script = [("admit", [5], 5)] + [("turn", True)] * 5
    ex, _ = _drive(FetchingSession(ready=ready, slots=1), script)
    snap, col, rows = _rows(ex)
    assert list(rows[:, col["ready"]]) == [-1 if ready is None else int(ready)] * 4
    seg = ex.step_account()["untraced"]
    assert seg["ready_share"] == share   # over the three overlapped steps
    assert seg["overlapped_share"] == 0.75 and seg["live_mean"] == 1.0
    assert seg["host_ms"]["p50"] == pytest.approx(
        seg["period_ms"]["p50"] - 2 * TICK / 1e6)


# -- the idle span, under a real profiler -------------------------------------------


def test_an_executor_idle_since_before_the_trace_still_names_its_idle_time(tmp_path):
    """``sched.idle`` is a wait in slices: a server that went idle before
    ``start_trace`` writes idle events all the same (one unbounded wait was
    open when the profiler started and so was never written)."""
    ex = GenerativeInferenceExecutor(AheadSession(slots=1),
                                     registry=MetricsRegistry()).start()
    try:
        assert ex.wait_warm(20.0)
        time.sleep(2 * executor_mod._IDLE_SLICE_S)   # idle before the trace starts
        _, events = _profile(tmp_path, lambda: time.sleep(
            10 * executor_mod._IDLE_SLICE_S))
    finally:
        ex.stop(drain=True)
    assert len([e for e in events if e[0] == "sched.idle"]) >= 2
