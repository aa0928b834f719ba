"""ISSUE 28: one vocabulary of spans on the served path and a closed account
of every request.

The door's ``request_span`` is recorded after the response is written and its
phases tile ``[t_start, t_end]``; the executor's two running totals give every
request its ``interleave`` (other requests' prefills while it held a slot) and
``loop``; ``monitoring.trace.span`` carries ``request_id`` / ``step`` into the
profiler's trace, so a request joins its decode steps by number; the names are
one declared tuple; and each new reader under ``benchmark/metrics/`` reads
what the program now records (``None`` where it does not).
"""

import ast
import glob
import importlib.util
import json
import os
import sys
import threading
import time
import urllib.request

import pytest

from deeplearning4j_tpu.monitoring import MetricsRegistry, flight
from deeplearning4j_tpu.monitoring.trace import SERVING_SPANS, span
from deeplearning4j_tpu.serving import JsonModelServer, StepAtDispatch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

GENERATIVE_PHASES = ["read", "parse", "queue", "prefill", "decode",
                     "interleave", "loop", "handoff", "serialize", "write"]


class SleepySession(StepAtDispatch):
    """A slot pool whose ``admit`` and ``step`` only sleep: the loop
    thread's time is then known, whatever the host is doing."""

    max_len = None
    eos_id = None

    def __init__(self, slots=3, admit_s=0.03, step_s=0.01, fetch_s=None):
        self.slots, self.admit_s, self.step_s = slots, admit_s, step_s
        self.fetch_s = fetch_s  # the part of a step spent under ``kv.step.fetch``
        self.active = set()

    @property
    def free_slots(self):
        return self.slots - len(self.active)

    def admit(self, prompt, max_new_tokens):
        time.sleep(self.admit_s)
        slot = min(set(range(self.slots)) - self.active)
        self.active.add(slot)
        return slot, 1

    def step(self):
        if self.fetch_s is None:
            time.sleep(self.step_s)
        else:   # as the paged pool: the wait for the device is a span
            time.sleep(self.step_s - self.fetch_s)
            with span("kv.step.fetch"):
                time.sleep(self.fetch_s)
        return {s: [2] for s in self.active}

    def release(self, slot):
        self.active.remove(slot)


def _post(port, rid, tokens=(1, 2, 3), max_new=None):
    headers = {"Content-Type": "application/json", "X-Request-Id": rid}
    if max_new is not None:
        headers["X-Max-New-Tokens"] = str(max_new)
    req = urllib.request.Request(f"http://127.0.0.1:{port}/predict",
                                 data=json.dumps(list(tokens)).encode(),
                                 headers=headers)
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())["output"]


def _serve(session, budgets, gap_s=0.02, **server_kw):
    """Send one request per budget, ``gap_s`` apart, through the door of a
    server over ``session``; return {request id: its request_span}."""
    rec = flight.FlightRecorder(proc="span-test", capacity=4096)
    flight.set_flight_recorder(rec)
    server = JsonModelServer(None, generative_session=session,
                             default_max_new_tokens=4,
                             registry=MetricsRegistry(), **server_kw).start()
    try:
        assert server.wait_ready(60.0)
        threads = [threading.Thread(target=_post, args=(
            server.port, f"r{i}"), kwargs={"max_new": n})
            for i, n in enumerate(budgets)]
        for th in threads:
            th.start()
            time.sleep(gap_s)
        for th in threads:
            th.join(60.0)
            assert not th.is_alive()
        stats = server._executor.stats()
    finally:
        server.stop()  # waits for the handlers: each records AFTER its response
        flight.set_flight_recorder(None)
    spans = {e["request_id"]: e for e in rec.events()
             if e["kind"] == "request_span"}
    assert set(spans) == {f"r{i}" for i in range(len(budgets))}
    return spans, stats


@pytest.fixture(scope="module")
def served():
    """Seven requests of unequal budgets through three slots: some are
    admitted while others decode, some wait for a slot."""
    return _serve(SleepySession(), budgets=[9, 4, 6, 3, 8, 5, 2])


def test_phases_tile_the_span_in_order(served):
    spans, _ = served
    for ev in spans.values():
        assert ev["outcome"] == "ok" and ev["code"] == 200
        assert list(ev["phases"]) == GENERATIVE_PHASES
        assert all(v >= 0.0 for v in ev["phases"].values()), ev["phases"]
        assert sum(ev["phases"].values()) == pytest.approx(
            ev["t_end"] - ev["t_start"], abs=1e-6)
        assert ev["t_end"] <= ev["t"]  # recorded once the response was written


def _slot_life(ev):
    """(admission's start, slot life's start, its end) on flight's clock."""
    p = ev["phases"]
    admit = ev["t_start"] + p["read"] + p["parse"] + p["queue"]
    slot = admit + p["prefill"]
    return admit, slot, slot + p["decode"] + p["interleave"] + p["loop"]


def test_interleave_is_the_prefill_of_those_admitted_meanwhile(served):
    spans, _ = served
    someone_stood_still = False
    for rid, ev in spans.items():
        _, slot, end = _slot_life(ev)
        others = 0.0
        for other_id, other in spans.items():
            a, s, _ = _slot_life(other)
            if other_id != rid and a >= slot - 1e-6 and s <= end + 1e-6:
                others += other["phases"]["prefill"]
        assert ev["phases"]["interleave"] == pytest.approx(others, abs=1e-6)
        someone_stood_still |= others > 0.0
    assert someone_stood_still  # the fixture did interleave prefills


def test_decode_is_the_requests_own_steps_and_joins_them_by_number(served):
    spans, stats = served
    for ev in spans.values():
        assert ev["steps"] == len(ev["step_ms"])
        assert ev["last_step"] - ev["first_step"] + 1 == ev["steps"]
        assert ev["phases"]["decode"] == pytest.approx(
            sum(ev["step_ms"]) / 1e3, abs=1e-5 * ev["steps"])  # step_ms rounds
        assert ev["phases"]["decode"] >= ev["steps"] * 0.01
    assert max(ev["last_step"] for ev in spans.values()) == stats["steps"]


def test_step_host_ms_is_the_step_less_the_pools_fetch():
    """A step's ``step_host_ms`` is its period less the ``kv.step.fetch``
    that closed inside it: the number the loop's step account keeps in the
    step's row (by ``first_step..last_step``), not an attribute of the pool."""
    session = SleepySession(slots=2, admit_s=0.0, step_s=0.02, fetch_s=0.015)
    rec = flight.FlightRecorder(proc="span-test", capacity=4096)
    flight.set_flight_recorder(rec)
    server = JsonModelServer(None, generative_session=session,
                             default_max_new_tokens=4,
                             registry=MetricsRegistry()).start()
    try:
        assert server.wait_ready(60.0)
        for rid in ("r0", "r1"):
            assert len(_post(server.port, rid, max_new=3)) == 3
        snap = server._executor._account.snapshot()
    finally:
        server.stop()
        flight.set_flight_recorder(None)
    col = {name: i for i, name in enumerate(snap["columns"])}
    rows = {int(r[col["step"]]): r for r in snap["rows"]}
    spans = [e for e in rec.events() if e["kind"] == "request_span"]
    assert len(spans) == 2
    for ev in spans:
        steps = range(ev["first_step"], ev["last_step"] + 1)
        assert len(ev["step_host_ms"]) == len(ev["step_ms"]) == len(steps) == 2
        for n, host, whole in zip(steps, ev["step_host_ms"], ev["step_ms"]):
            period, fetch = rows[n][col["period"]], rows[n][col["kv.step.fetch"]]
            assert fetch > 0 and whole == round(period / 1e6, 3)
            assert host == round((period - fetch) / 1e6, 3)
    plain, _ = _serve(SleepySession(slots=1), budgets=[2])
    assert "step_host_ms" not in plain["r0"]  # a pool that opens no fetch span


def test_a_request_done_at_prefill_has_no_steps_to_join():
    spans, _ = _serve(SleepySession(slots=1), budgets=[1])
    ev = spans["r0"]
    assert list(ev["phases"]) == GENERATIVE_PHASES
    assert ev["steps"] == 0 and "first_step" not in ev
    assert ev["phases"]["decode"] == 0.0 and ev["phases"]["interleave"] == 0.0


def test_span_carries_its_stats_parent_and_clock():
    with span("outer", request_id="r-1") as outer:
        with span("inner", step=3, live=2) as inner:
            time.sleep(0.002)
    assert inner.qualified_name == "outer/inner"
    assert inner.stats == {"step": 3, "live": 2}
    assert outer.stats == {"request_id": "r-1"}
    assert outer.start_ns <= inner.start_ns
    assert inner.duration_s >= 0.002 and outer.duration_s >= inner.duration_s


def _profile(tmp_path, body):
    """Run ``body`` inside a profiler session; return its host events as
    (name, stats, start_ns, end_ns)."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = body()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert files, "the profiler wrote no trace"
    events = []
    for plane in ProfileData.from_file(files[0]).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in SERVING_SPANS:
                    events.append((e.name, dict(e.stats), e.start_ns,
                                   e.start_ns + e.duration_ns))
    return out, events


def test_profiler_trace_joins_requests_to_steps_and_prefills(tmp_path):
    """On the profiler's own clock: ``sched.decode_step`` events whose
    ``step`` covers each request's ``first_step..last_step``, a
    ``sched.admit`` per id sent with the pool's ``kv.prefill`` inside it, the
    step's three ``kv.step.*`` spans, and the door's spans per request."""
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
    (spans, totals), events = _profile(tmp_path, lambda: _serve(
        pool, budgets=[4, 3, 5], warmup_input=[1, 2]))

    by_name = {}
    for name, stats, start, end in events:
        by_name.setdefault(name, []).append((stats, start, end))
    steps_traced = {int(s["step"]) for s, _, _ in by_name["sched.decode_step"]}
    for ev in spans.values():
        assert set(range(ev["first_step"], ev["last_step"] + 1)) <= steps_traced
        assert len(ev["step_host_ms"]) == ev["steps"]  # the pool's fetch time
    assert all(int(s["live"]) >= 1 for s, _, _ in by_name["sched.decode_step"])

    admits = {s["request_id"]: (start, end)
              for s, start, end in by_name["sched.admit"]}
    assert set(admits) == set(spans)
    assert all(int(s["prompt_len"]) == 3 for s, _, _ in by_name["sched.admit"])
    prefills = by_name["kv.prefill"]
    for start, end in admits.values():  # each admission holds one prefill
        inside = [s for s, a, b in prefills if start <= a and b <= end]
        assert len(inside) == 1 and int(inside[0]["bucket"]) == 16
        assert {"shared_blocks", "new_blocks"} <= set(inside[0])
    assert len(by_name["kv.prefill.fetch"]) == len(prefills)
    # a turn of the loop dispatches step n+1, then collects and retires step
    # n (ISSUE 38): it holds at most one of each, an upload with its dispatch
    # and a fetch with its retire; the turn that starts a run only dispatches,
    # the one that ends it only collects, and every step is in some turn
    held = {name: [sum(1 for _, a, b in by_name[name] if start <= a and b <= end)
                   for _, start, end in by_name["sched.decode_step"]]
            for name in ("kv.step.upload", "kv.step.dispatch", "kv.step.fetch",
                         "sched.retire")}
    for name, counts in held.items():
        assert max(counts) == 1 and sum(counts) == totals["steps"], name
    assert held["kv.step.upload"] == held["kv.step.dispatch"]
    assert held["kv.step.fetch"] == held["sched.retire"]
    assert any(d and f for d, f in zip(held["kv.step.dispatch"],
                                       held["kv.step.fetch"]))  # one step ahead
    assert {s["request_id"] for s, _, _ in by_name["door.request"]} >= set(spans)
    for name in ("door.read", "door.parse", "door.wait", "door.serialize",
                 "door.write"):
        assert len(by_name[name]) >= len(spans), name


def _span_literals(path):
    """The first argument of every ``span(...)`` call in a source file."""
    names = []
    for node in ast.walk(ast.parse(open(path).read(), path)):
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "span" and node.args):
            arg = node.args[0]
            assert isinstance(arg, ast.Constant) and isinstance(arg.value, str), (
                f"{path}:{node.lineno}: a served-path span's name is a literal")
            names.append(arg.value)
    return names


def test_served_path_span_names_are_the_declared_vocabulary():
    pkg = os.path.join(ROOT, "deeplearning4j_tpu")
    used = set()
    for path in glob.glob(os.path.join(pkg, "serving", "*.py")) + [
            os.path.join(pkg, "models", "paged_decode.py")]:
        used |= set(_span_literals(path))
    assert used == set(SERVING_SPANS)
    assert len(SERVING_SPANS) == len(set(SERVING_SPANS))
    doc = open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")).read()
    missing = [n for n in SERVING_SPANS if f"`{n}`" not in doc]
    assert not missing, f"not tabled in docs/OBSERVABILITY.md: {missing}"


def test_a_steps_routing_counters_ride_the_dispatch_span_and_are_tabled():
    """ISSUE 31: ``kv.step.dispatch`` carries what the family's last fetched
    step counted (``last_step_stats``: for the kimi_k2 family its routing),
    and the table of spans names every such stat in that span's row."""
    from deeplearning4j_tpu.models.kimi_k2 import MOE_STATS

    path = os.path.join(ROOT, "deeplearning4j_tpu", "models", "paged_decode.py")
    dispatch = [node for node in ast.walk(ast.parse(open(path).read(), path))
                if isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "span" and node.args
                and node.args[0].value == "kv.step.dispatch"]
    assert len(dispatch) == 1
    named = {kw.arg for kw in dispatch[0].keywords if kw.arg}
    spread = [kw.value for kw in dispatch[0].keywords if kw.arg is None]
    assert named == {"live_blocks", "mapped_blocks"}
    # ``grouped``: what a pool with a windowed cache group counts a step
    # (ISSUE 37: ``_slide_windows``), empty for every other pool
    assert [ast.unparse(v) for v in spread] == ["grouped", "self.last_step_stats"]
    doc = open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")).read()
    row = next(line for line in doc.splitlines()
               if line.startswith("| `kv.step.dispatch`"))
    assert "`live_blocks_g<i>`" in row and "`window_blocks_freed`" in row
    assert {"routed_tokens", "resident_assignments", "experts_touched"} <= set(MOE_STATS)
    missing = [n for n in MOE_STATS if f"`{n}`" not in row]
    assert not missing, f"not in the span's row of docs/OBSERVABILITY.md: {missing}"


# -- the readers under benchmark/metrics/ ------------------------------------


def _read(metric, obs):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)  # the readers import ``benchmark.reduce``
    spec = importlib.util.spec_from_file_location(
        "metric_under_test_" + metric.replace(".", "_"),
        os.path.join(ROOT, "benchmark", "metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(obs)


def _obs(spans, records=None):
    records = records if records is not None else [
        {"id": rid, "in_window": True, "ok": True, "sent": 1.0, "done": 2.0}
        for rid in spans]
    return {"serve": {"window": {"records": records}, "spans": spans}}


def _closed(interleave, loop, steps, door=0.001, **extra):
    """A 200's span whose phases sum to 0.9 s + its five door phases."""
    phases = {"read": door, "parse": door, "queue": 0.1, "prefill": 0.05,
              "decode": 0.75 - interleave - loop, "interleave": interleave,
              "loop": loop, "handoff": door, "serialize": door, "write": door}
    return {"code": 200, "t_start": 10.0, "t_end": 10.0 + sum(phases.values()),
            "phases": phases, "steps": steps, **extra}


# what the parent commit recorded: no door phases, no account, no t_start
OLD_SPAN = {"code": 200, "steps": 4, "step_ms": [10.0] * 4,
            "phases": {"queue": 0.1, "prefill": 0.05, "decode": 0.04,
                       "serialize": 0.001}}

def _account(rows=900, ready_share=0.25, **segments):
    """An observation whose ``/stats`` holds a step account: the ``untraced``
    segment as a 3 ms period of which the fetch is 0.8."""
    def q(p50):
        return {"p50": p50, "p90": 2 * p50}

    untraced = {
        "steps": rows, "rows": rows, "live_mean": 1.3, "overlapped_share": 0.95,
        "ready_share": ready_share, "period_ms": q(3.0), "host_ms": q(2.2),
        "other_ms": q(0.06), "loop_s": {"sched.idle": 9.0},
        "phases_ms": {"kv.step.prepare": q(0.05), "kv.step.upload": q(0.8),
                      "kv.step.dispatch": q(0.75), "kv.step.fetch": q(0.8),
                      "sched.retire": q(0.04), "sched.gauges": q(0.07),
                      "sched.decode_step": q(0.1)}}
    return {"serve": {"executor_stats": {"step_account": {
        "untraced": untraced, **segments}}}}


# ten readers of the ``untraced`` segment (ISSUE 39), each with its value on
# the account above; all of them None where the program keeps no account
# (every commit before PR 39), where the segment is absent or short
STEP_ACCOUNT_READERS = {
    "kv.step_period_ms.untraced": 3.0, "kv.step_host_ms.untraced": 2.2,
    "kv.step_prepare_ms.untraced": 0.05, "kv.step_upload_ms.untraced": 0.8,
    "kv.step_launch_ms.untraced": 0.75, "kv.step_fetch_wait_ms.untraced": 0.8,
    "sched.retire_ms.untraced": 0.04, "sched.gauges_ms.untraced": 0.07,
    "sched.step_unattributed_share": 2.0, "kv.device_paced_step_share": 75.0}
NO_ACCOUNT = {"serve": {"executor_stats": {"steps": 12, "blocks": {}}}}
TRACED_ONLY = {"serve": {"executor_stats": {"step_account": {
    "traced": _account()["serve"]["executor_stats"]["step_account"]["untraced"]}}}}

CLOSED = {"a": _closed(0.08, 0.02, 5, step_host_ms=[2.0, 4.0]),
          "b": _closed(0.30, 0.00, 10, step_host_ms=[3.0]),
          "c": _closed(0.00, 0.06, 2, door=0.003, step_host_ms=[9.0, 1.0])}


@pytest.mark.parametrize("metric,obs,expected", [
    # per request (0.08+0.02)/5, 0.30/10, 0.06/2 s a step -> 20, 30, 30 ms
    ("sched.stall_per_tok_p50_ms", _obs(CLOSED), 30.0),
    ("sched.stall_per_tok_p50_ms", _obs({"a": OLD_SPAN}), None),
    ("door.self_p50_ms", _obs(CLOSED), 5.0),  # 5, 5 and 15 ms
    ("door.self_p50_ms", _obs({"a": OLD_SPAN}), None),
    # 0.905 s (0.915 for c) accounted of the client's 1.0 s
    ("door.span_coverage_p50", _obs(CLOSED), 90.5),
    ("door.span_coverage_p50", _obs({"a": OLD_SPAN}), None),
    ("kv.step_host_ms", _obs(CLOSED), 3.0),  # median of 2, 4, 3, 9, 1
    ("kv.step_host_ms", _obs({"a": OLD_SPAN}), None),
    # 0.38 s of the 3 x 0.75 s the three held a slot
    ("sched.interleave_time_share", _obs(CLOSED), 100.0 * 0.38 / 2.25),
    ("sched.interleave_time_share", _obs({"a": OLD_SPAN}), None),
    ("sched.interleave_time_share", {"serve": None}, None),
    *[(m, _account(), want) for m, want in STEP_ACCOUNT_READERS.items()],
    *[(m, NO_ACCOUNT, None) for m in STEP_ACCOUNT_READERS],
    *[(m, _account(rows=199), None) for m in STEP_ACCOUNT_READERS],
    ("kv.step_period_ms.untraced", TRACED_ONLY, None),   # no untraced segment
    ("kv.step_host_ms.untraced", {"serve": None}, None),
    ("kv.device_paced_step_share", _account(ready_share=None), None),  # none said
    ("kv.device_paced_step_share", _account(ready_share=0.0), 100.0),
    ("sched.gauges_ms.untraced", _account(rows=200), 0.07),   # 200 rows do
])
def test_new_reader_on_a_hand_built_observation(metric, obs, expected):
    got = _read(metric, obs)
    if expected is None:
        assert got is None
    else:
        assert got == pytest.approx(expected, rel=1e-9)


def test_readers_leave_out_what_is_outside_the_window_or_failed():
    records = [{"id": "a", "in_window": True, "ok": True, "sent": 1.0, "done": 2.0},
               {"id": "b", "in_window": False, "ok": True, "sent": 1.0, "done": 2.0},
               {"id": "c", "in_window": True, "ok": False, "sent": 1.0, "done": 2.0}]
    obs = _obs(CLOSED, records)
    assert _read("sched.stall_per_tok_p50_ms", obs) == pytest.approx(20.0)
    assert _read("kv.step_host_ms", obs) == pytest.approx(3.0)  # 2 and 4


def test_every_new_metric_of_benchmark_json_has_its_reader():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = {w["name"] for w in bench["workloads"]}
    reports = {m["name"]: set(m.get("workloads", cells))
               for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           m["name"] + ".py")), m["name"]
        # a cell a metric lists reports the end-to-end metric it moves
        assert set(m.get("workloads", [])) <= reports[m["moves"]], m["name"]
    # ISSUE 39's ten: appended together, read from the program's spans with
    # no profiler listening, in the four serving cells, for their p90
    names = [m["name"] for m in bench["per_layer"]]
    mine = bench["per_layer"][names.index("kv.step_period_ms.untraced"):][:10]
    assert {m["name"] for m in mine} == set(STEP_ACCOUNT_READERS)
    serving = [w["name"] for w in bench["workloads"]
               if w["name"] in reports["serve_lat_per_tok_p90_ms"]]
    for m in mine:
        assert (m["source"], m["moves"], m["workloads"]) == (
            "program_span", "serve_lat_per_tok_p90_ms", serving), m["name"]
        assert m["layer"] == m["name"].split(".")[0] and m["unit"] in ("ms", "%")


def test_leak_audit_counts_only_this_workers_shared_memory(monkeypatch, tmp_path):
    """/dev/shm is shared by the xdist workers: a segment that a live process
    outside this worker's tree made is that worker's, not this test's leak
    (the teardown errors of the driver's run on the parent commit). The audit
    is pointed at a directory of this test's own, so nothing is made in
    /dev/shm, where another run's audit would find it."""
    # by path: `import conftest` is whichever conftest.py pytest loaded last
    spec = importlib.util.spec_from_file_location(
        "tests_conftest", os.path.join(ROOT, "tests", "conftest.py"))
    conftest = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(conftest)

    mine = f"tdl_etl_{os.getpid()}_spantest"
    foreign = "tdl_etl_1_spantest"  # pid 1 lives and is no child of ours
    orphan = "tdl_etl_4194305_spantest"  # above pid_max: its creator is gone
    for name in (mine, foreign, orphan, "tdl_nopid", "other_etl_1_x"):
        (tmp_path / name).write_bytes(b"")
    monkeypatch.setattr(conftest, "_SHM_DIR", str(tmp_path))
    assert conftest._tdl_shm_segments() == {mine, orphan, "tdl_nopid"}
