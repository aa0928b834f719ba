"""Cluster-wide observability plane (ISSUE 7).

Layer 1 (aggregation): per-process registry spools merged into ONE
proc/rank-labeled /metrics with derived straggler gauges. Layer 2 (flight
recorder): bounded event rings merged into a monotonic-ordered
postmortem.json on gang failure. Layer 3 (attribution): per-step
input/h2d/compute/collective breakdown through monitoring.trace.

Satellites covered here: the strict Prometheus round-trip (escaping), the
wall-clock AST lint, registry-across-spawn isolation, the last-failure
info gauge, and bench.py's --check-telemetry contract.

The slow tier spawns real 2-process gangs under GangSupervisor — the
acceptance runs for the aggregated scrape + skew gauge and for the
crash postmortem.
"""

import ast
import json
import multiprocessing
import os
import pathlib
import re
import time
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.monitoring import aggregate, flight
from deeplearning4j_tpu.monitoring.aggregate import (MetricsSpooler,
                                                     derive_straggler,
                                                     merged_prometheus)
from deeplearning4j_tpu.monitoring.flight import FlightRecorder, merge_events
from deeplearning4j_tpu.monitoring.registry import MetricsRegistry
from deeplearning4j_tpu.monitoring.trace import StepPhaseRecorder

WORKERS = os.path.join(os.path.dirname(__file__), "mp_workers.py")
ROOT = pathlib.Path(__file__).resolve().parent.parent


# ------------------------------------------------------- strict text parser


_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_ESCAPES = {"\\": "\\", "n": "\n", '"': '"'}


def _parse_sample(line):
    """One sample line, strictly: name{label="value",...} value. Raises on
    anything a real Prometheus scraper would reject."""
    brace = line.find("{")
    if brace == -1:
        name, _, value = line.partition(" ")
        assert _NAME_RE.match(name), f"bad metric name {name!r}"
        return name, (), float(value)
    name = line[:brace]
    assert _NAME_RE.match(name), f"bad metric name {name!r}"
    labels = []
    j = brace + 1
    while line[j] != "}":
        eq = line.index("=", j)
        key = line[j:eq]
        assert _NAME_RE.match(key), f"bad label name {key!r}"
        assert line[eq + 1] == '"', f"unquoted label value in {line!r}"
        j = eq + 2
        buf = []
        while True:
            c = line[j]
            if c == "\\":
                esc = line[j + 1]
                assert esc in _ESCAPES, f"bad escape \\{esc} in {line!r}"
                buf.append(_ESCAPES[esc])
                j += 2
            elif c == '"':
                j += 1
                break
            else:
                buf.append(c)
                j += 1
        labels.append((key, "".join(buf)))
        if line[j] == ",":
            j += 1
    rest = line[j + 1:]
    assert rest.startswith(" "), f"missing space before value in {line!r}"
    return name, tuple(labels), float(rest.strip())


def _parse_prometheus(text):
    """{sample_name: {labels_tuple: value}} with full-format validation."""
    assert text == "" or text.endswith("\n"), "exposition must end in newline"
    out = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            body = line.split(" ", 3)
            assert _NAME_RE.match(body[2]), f"bad name in comment {line!r}"
            continue
        assert not line.startswith("#"), f"unknown comment {line!r}"
        name, labels, value = _parse_sample(line)
        out.setdefault(name, {})[labels] = value
    return out


# ------------------------------------------------- registry escaping (sat 2)


def test_prometheus_escaping_round_trip():
    reg = MetricsRegistry()
    nasty = 'back\\slash"quote"\nnewline'
    reg.counter("tdl_esc_total", "counts\nwith a newline and \\slash in help",
                labels=("path",)).labels(nasty).inc(3)
    reg.gauge("tdl_esc_gauge", labels=("p",)).labels("plain").set(1.5)
    reg.histogram("tdl_esc_hist", labels=("p",),
                  buckets=(0.1, 1.0)).labels(nasty).observe(0.5)
    text = reg.to_prometheus()
    parsed = _parse_prometheus(text)  # raises on any malformed line
    assert parsed["tdl_esc_total"][(("path", nasty),)] == 3
    assert parsed["tdl_esc_gauge"][(("p", "plain"),)] == 1.5
    # histogram children carry the escaped labels too, plus le
    assert parsed["tdl_esc_hist_bucket"][(("p", nasty), ("le", "1"))] == 1
    assert parsed["tdl_esc_hist_count"][(("p", nasty),)] == 1


def test_registry_label_name_validation():
    reg = MetricsRegistry()
    with pytest.raises(ValueError, match="invalid label name"):
        reg.counter("tdl_bad_total", labels=('quo"te',))
    with pytest.raises(ValueError, match="invalid label name"):
        reg.gauge("tdl_bad_gauge", labels=("0startsdigit",))


def test_registry_clear_children():
    reg = MetricsRegistry()
    g = reg.gauge("tdl_info", labels=("reason",))
    g.labels("crash").set(1)
    g.labels("hang").set(2)
    assert len(g.snapshot()["series"]) == 2
    g.clear_children()
    g.labels("bind").set(3)
    series = g.snapshot()["series"]
    assert len(series) == 1 and series[0]["labels"] == {"reason": "bind"}


# -------------------------------------------------------- flight recorder


def test_flight_ring_is_bounded_and_ordered():
    rec = FlightRecorder(proc="t", capacity=4)
    for i in range(10):
        rec.record("step_begin", iteration=i)
    evs = rec.events()
    assert len(evs) == 4
    assert [e["iteration"] for e in evs] == [6, 7, 8, 9]
    ts = [e["t"] for e in evs]
    assert ts == sorted(ts)
    assert all(e["proc"] == "t" and e["kind"] == "step_begin" for e in evs)


def test_flight_spool_and_merge(tmp_path):
    a = FlightRecorder(proc="rank0", directory=str(tmp_path), interval=0.0)
    b = FlightRecorder(proc="rank1", directory=str(tmp_path), interval=0.0)
    a.record("step_begin", iteration=0)
    b.record("step_begin", iteration=0)
    a.record("step_end", iteration=0)
    spools = flight.read_spools(str(tmp_path))
    assert {s["proc"] for s in spools} == {"rank0", "rank1"}
    sup = FlightRecorder(proc="supervisor")
    sup.record("gang_failure", reason="crash")
    merged = merge_events(spools, sup.events())
    assert len(merged) == 4
    ts = [e["t"] for e in merged]
    assert ts == sorted(ts)
    assert merged[-1]["kind"] == "gang_failure"


def test_flight_env_contract(tmp_path, monkeypatch):
    monkeypatch.delenv(flight.ENV_DIR, raising=False)
    flight.set_flight_recorder(None)
    assert not flight.active()
    assert flight.record("noop") is None  # no dir: nothing recorded
    monkeypatch.setenv(flight.ENV_DIR, str(tmp_path))
    monkeypatch.setenv(flight.ENV_INTERVAL, "0")
    monkeypatch.setenv(flight.ENV_RANK, "3")
    assert flight.active()
    flight.record("heartbeat", iteration=5)
    spools = flight.read_spools(str(tmp_path))
    assert len(spools) == 1 and spools[0]["proc"] == "rank3"
    assert spools[0]["events"][0]["kind"] == "heartbeat"


def test_fault_injector_records_flight_event(tmp_path, monkeypatch):
    """slow_ckpt_io honors rank= (the straggler fault) and crash/hang leave
    a fault_injected breadcrumb; the crash itself is not executed here —
    the slow-path rank gate is what's under test."""
    from deeplearning4j_tpu.common.faults import FaultInjector, parse_fault_spec

    inj = FaultInjector(parse_fault_spec("slow_ckpt_io@value=0.4,rank=1"),
                        rank=0, incarnation=0)
    t0 = time.perf_counter()
    inj.fire("ckpt_write")  # wrong rank: no sleep (generous load margin)
    assert time.perf_counter() - t0 < 0.3
    inj = FaultInjector(parse_fault_spec("slow_ckpt_io@value=0.4,rank=1"),
                        rank=1, incarnation=0)
    t0 = time.perf_counter()
    inj.fire("ckpt_write")
    assert time.perf_counter() - t0 >= 0.4
    # legacy value-form still fires on every rank
    inj = FaultInjector(parse_fault_spec("slow_ckpt_io=0.05"), rank=7,
                        incarnation=2)
    t0 = time.perf_counter()
    inj.fire("ckpt_write")
    assert time.perf_counter() - t0 >= 0.05


# ---------------------------------------------------- aggregation (layer 1)


def _rank_registry(step_seconds, steps=5):
    reg = MetricsRegistry()
    h = reg.histogram("tdl_step_wall_seconds", "wall", labels=("trainer",))
    for _ in range(steps):
        h.labels("ParallelTrainer").observe(step_seconds)
    reg.counter("tdl_iterations_total", labels=("model",)).labels("M").inc(steps)
    return reg


def test_spooler_writes_and_merges_with_rank_labels(tmp_path):
    MetricsSpooler(str(tmp_path), proc="rank0", registry=_rank_registry(0.01),
                   interval=0.0, rank=0).spool(force=True)
    MetricsSpooler(str(tmp_path), proc="rank1", registry=_rank_registry(0.04),
                   interval=0.0, rank=1).spool(force=True)
    local = MetricsRegistry()
    local.counter("tdl_gang_restarts_total", "restarts").inc()
    text = merged_prometheus(str(tmp_path), local_registry=local,
                             local_proc="supervisor")
    parsed = _parse_prometheus(text)  # strict: the merge must render validly
    counts = parsed["tdl_step_wall_seconds_count"]
    ranks = {dict(k).get("rank") for k in counts}
    assert ranks == {"0", "1"}  # same family, distinct rank labels
    procs = {dict(k).get("proc") for k in counts}
    assert procs == {"rank0", "rank1"}
    assert parsed["tdl_gang_restarts_total"][(("proc", "supervisor"),)] == 1
    # derived straggler gauges ride the merge
    assert parsed["tdl_step_time_skew_ratio"][()] == pytest.approx(4.0)
    assert parsed["tdl_step_time_slowest_rank"][()] == 1
    assert parsed["tdl_step_time_mean_seconds"][(("rank", "1"),)] == pytest.approx(0.04)


def test_read_spools_keeps_newest_per_proc(tmp_path):
    old = {"proc": "rank0", "rank": 0, "pid": 1, "wall": 100.0, "snapshot": {}}
    new = {"proc": "rank0", "rank": 0, "pid": 2, "wall": 200.0,
           "snapshot": {"x": {"type": "counter", "series": []}}}
    for pid, payload in ((1, old), (2, new)):
        with open(tmp_path / f"{aggregate.SPOOL_PREFIX}rank0.{pid}.json", "w") as f:
            json.dump(payload, f)
    (tmp_path / f"{aggregate.SPOOL_PREFIX}torn.3.json").write_text("{nope")
    spools = aggregate.read_spools(str(tmp_path))
    assert len(spools) == 1 and spools[0]["pid"] == 2  # newest wins, torn skipped


def test_derive_straggler_requires_two_ranks():
    spool = lambda rank, mean: {  # noqa: E731
        "rank": rank,
        "snapshot": {"tdl_step_wall_seconds": {
            "type": "histogram",
            "series": [{"count": 4, "sum": 4 * mean}]}}}
    assert derive_straggler([spool(0, 0.01)]) is None
    d = derive_straggler([spool(0, 0.01), spool(1, 0.05), spool(2, 0.02)])
    assert d["slowest_rank"] == 1
    assert d["skew_ratio"] == pytest.approx(5.0)
    assert d["mean_step_seconds"] == {0: pytest.approx(0.01),
                                      1: pytest.approx(0.05),
                                      2: pytest.approx(0.02)}


def test_maybe_spool_env_contract(tmp_path, monkeypatch):
    monkeypatch.delenv(aggregate.ENV_DIR, raising=False)
    aggregate.maybe_spool()  # no env: no-op
    assert not list(tmp_path.iterdir())
    monkeypatch.setenv(aggregate.ENV_DIR, str(tmp_path))
    monkeypatch.setenv(aggregate.ENV_INTERVAL, "0")
    aggregate.maybe_spool(force=True)
    spools = aggregate.read_spools(str(tmp_path))
    assert len(spools) == 1 and spools[0]["pid"] == os.getpid()


def test_ui_server_serves_merged_metrics(tmp_path):
    from deeplearning4j_tpu.ui.server import UIServer

    MetricsSpooler(str(tmp_path), proc="rank0", registry=_rank_registry(0.01),
                   interval=0.0, rank=0).spool(force=True)
    MetricsSpooler(str(tmp_path), proc="rank1", registry=_rank_registry(0.03),
                   interval=0.0, rank=1).spool(force=True)
    ui = UIServer(port=0)
    try:
        ui.attach_spool_dir(str(tmp_path), local_proc="supervisor")
        base = f"http://127.0.0.1:{ui.port}"
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
            parsed = _parse_prometheus(r.read().decode())
        ranks = {dict(k).get("rank")
                 for k in parsed["tdl_step_wall_seconds_count"]}
        # superset, not equality: the scraping process's OWN registry rides
        # the merge as proc="supervisor" with no rank label, and any earlier
        # test that ran a trainer leaves that series behind (order-dependent
        # flake otherwise — the spooled ranks are what's under test)
        assert {"0", "1"} <= ranks
        assert parsed["tdl_step_time_skew_ratio"][()] == pytest.approx(3.0)
        with urllib.request.urlopen(f"{base}/metrics.json", timeout=10) as r:
            snap = json.loads(r.read().decode())
        assert set(snap["procs"]) == {"rank0", "rank1"}
        assert snap["derived"]["slowest_rank"] == 1
        assert "local" in snap
    finally:
        ui.stop()


# ---------------------------------------------- step-time attribution (3)


def test_step_phase_recorder_exclusive_nesting():
    reg = MetricsRegistry()
    rec = StepPhaseRecorder(registry=reg)
    t0 = time.perf_counter()
    with rec.phase("compute"):
        time.sleep(0.03)
        with rec.phase("h2d"):
            time.sleep(0.03)
    outer = time.perf_counter() - t0
    rec.step_done()
    snap = reg.snapshot()["tdl_step_phase_seconds"]
    series = {s["labels"]["phase"]: s for s in snap["series"]}
    assert series["h2d"]["sum"] >= 0.03
    assert series["compute"]["sum"] >= 0.02
    # exclusive time: the nested h2d slice (≥0.03s by construction) is NOT
    # double-counted in compute — load-robust: compute ≤ outer − child sleep
    assert series["compute"]["sum"] <= outer - 0.029
    summary = rec.summary()
    assert summary["steps"] == 1
    total_pct = sum(p["pct"] for p in summary["phases"].values())
    assert total_pct == pytest.approx(100.0, abs=5.0)
    assert set(summary["phases"]) >= {"input", "h2d", "compute", "collective"}


def test_step_phase_summary_covers_wall():
    rec = StepPhaseRecorder(registry=MetricsRegistry())
    for _ in range(3):
        with rec.phase("input"):
            time.sleep(0.01)
        with rec.phase("compute"):
            time.sleep(0.02)
        rec.step_done()
    s = rec.summary()
    assert s["steps"] == 3
    pct = {k: v["pct"] for k, v in s["phases"].items()}
    assert pct["compute"] > pct["input"] > 0
    assert sum(pct.values()) + s["other_pct"] == pytest.approx(100.0, abs=1.0)
    # the loop is fully instrumented; generous bound for loaded CI hosts
    # (uninstrumented scheduling gaps between phases inflate "other")
    assert s["other_pct"] < 60.0


def test_step_phase_recorder_survives_raising_phase_body():
    """ISSUE 10 satellite: a phase body that raises must not corrupt the
    frame stack or the exclusive-time accounting of the surrounding step."""
    reg = MetricsRegistry()
    rec = StepPhaseRecorder(registry=reg)
    with pytest.raises(RuntimeError):
        with rec.phase("compute"):
            time.sleep(0.01)
            with rec.phase("h2d"):
                raise RuntimeError("h2d blew up")
    assert rec._depth == 0  # both phases unwound despite the raise
    rec.discard()  # failed step: drop its partial accumulation

    # the NEXT step accounts cleanly — nesting and exclusive time intact
    with rec.phase("compute"):
        time.sleep(0.02)
        with rec.phase("h2d"):
            time.sleep(0.02)
    rec.step_done()
    snap = reg.snapshot()["tdl_step_phase_seconds"]
    series = {s["labels"]["phase"]: s for s in snap["series"]}
    assert series["compute"]["count"] == 1  # the failed step left NO sample
    assert series["h2d"]["count"] == 1
    assert series["h2d"]["sum"] >= 0.02
    # exclusive: compute excludes the nested h2d slice
    assert series["compute"]["sum"] < 0.04


def test_step_phase_discard_after_failed_step_leaves_histograms_untouched():
    reg = MetricsRegistry()
    rec = StepPhaseRecorder(registry=reg)
    with rec.phase("input"):
        time.sleep(0.005)
    rec.step_done()  # one good step
    before = reg.snapshot()["tdl_step_phase_seconds"]
    with pytest.raises(ValueError):
        with rec.phase("input"):
            raise ValueError("iterator exploded")
    rec.discard()
    after = reg.snapshot()["tdl_step_phase_seconds"]
    assert after == before  # discard() observed nothing
    assert rec.summary()["steps"] == 1  # the failed step never counted


def test_parallel_trainer_emits_phases_and_step_wall():
    import jax
    from jax.sharding import Mesh

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.monitoring import get_registry
    from deeplearning4j_tpu.parallel.trainer import ParallelTrainer
    from tests.mp_workers import _global_batch, _toy_net

    reg = get_registry()
    base_phase = reg.get("tdl_step_phase_seconds")
    base_counts = ({s["labels"]["phase"]: s["count"]
                    for s in base_phase.snapshot()["series"]}
                   if base_phase else {})
    net = _toy_net()
    trainer = ParallelTrainer(net, Mesh(np.array(jax.devices()[:2]), ("data",)))
    x, y = _global_batch(0)
    trainer.fit([DataSet(x, y), DataSet(x, y), DataSet(x, y)])
    counts = {s["labels"]["phase"]: s["count"]
              for s in reg.get("tdl_step_phase_seconds").snapshot()["series"]}
    assert counts.get("compute", 0) - base_counts.get("compute", 0) == 3
    assert counts.get("input", 0) > base_counts.get("input", 0)
    wall = reg.get("tdl_step_wall_seconds").snapshot()["series"]
    assert any(s["labels"]["trainer"] == "ParallelTrainer" and s["count"] >= 2
               for s in wall)


# ------------------------------------------ supervisor failure bookkeeping


def _offline_supervisor(tmp_path, registry):
    from deeplearning4j_tpu.parallel.supervisor import GangSupervisor

    return GangSupervisor("x:y", n_processes=2, registry=registry,
                          workdir=str(tmp_path / "gang"))


def test_supervisor_last_failure_info_gauge(tmp_path):
    from deeplearning4j_tpu.parallel.supervisor import GangEvent

    reg = MetricsRegistry()
    sup = _offline_supervisor(tmp_path, reg)
    sup._note_failure(GangEvent(time.monotonic(), "crash", 0, (1,), 7))
    snap = reg.snapshot()["tdl_gang_last_failure_info"]
    assert len(snap["series"]) == 1
    assert snap["series"][0]["labels"] == {"reason": "crash", "rank": "1",
                                          "iteration": "7"}
    assert sup.last_failure["reason"] == "crash"
    # a second failure REPLACES the series (one-series info gauge)
    sup.restarts = 1
    sup._note_failure(GangEvent(time.monotonic(), "hang", 1, (0,), 9))
    snap = reg.snapshot()["tdl_gang_last_failure_info"]
    assert len(snap["series"]) == 1
    assert snap["series"][0]["labels"]["reason"] == "hang"
    assert snap["series"][0]["value"] == 1  # restarts at failure time


def test_supervisor_postmortem_merges_spools(tmp_path):
    from deeplearning4j_tpu.parallel.supervisor import GangEvent

    sup = _offline_supervisor(tmp_path, MetricsRegistry())
    sup.flight_dir = str(tmp_path / "flight")
    for rank in (0, 1):
        rec = FlightRecorder(proc=f"rank{rank}", directory=sup.flight_dir,
                             interval=0.0)
        rec.record("step_begin", iteration=6)
        rec.record("step_end", iteration=6, loss=0.5)
    FlightRecorder(proc="rank1", directory=sup.flight_dir,
                   interval=0.0).record("step_begin", iteration=7)
    failure = GangEvent(time.monotonic(), "crash", 0, (1,), 7)
    sup._note_failure(failure)
    path = sup._write_postmortem(failure)
    with open(path) as f:
        pm = json.load(f)
    assert pm["classification"] == "crash" and pm["iteration"] == 7
    ts = [e["t"] for e in pm["events"]]
    assert ts == sorted(ts)  # monotonic merged timeline
    assert set(pm["procs"]) == {"rank0", "rank1", "supervisor"}
    r1 = [e for e in pm["events"] if e["proc"] == "rank1"]
    assert any(e["kind"] == "step_begin" and e["iteration"] == 7 for e in r1)
    assert any(e["kind"] == "gang_failure" for e in pm["events"])


# -------------------------------------- registry across spawn (satellite 4)


def _spawn_probe(out_path, spool_dir):
    """Child side: report registry contents at entry + spool path."""
    from deeplearning4j_tpu.monitoring.aggregate import MetricsSpooler
    from deeplearning4j_tpu.monitoring.registry import get_registry

    reg = get_registry()
    names_at_start = reg.names()
    reg.counter("tdl_spawn_child_total").inc()
    spooler = MetricsSpooler(spool_dir, proc="spawncheck", registry=reg,
                             interval=0.0)
    spooler.spool(force=True)
    with open(out_path, "w") as f:
        json.dump({"names_at_start": names_at_start,
                   "spool_path": spooler.path}, f)


def test_registry_clean_and_spool_collision_free_across_spawn(tmp_path):
    from deeplearning4j_tpu.monitoring import get_registry

    parent_reg = get_registry()
    parent_reg.counter("tdl_spawn_parent_total").inc(41)
    spool_dir = str(tmp_path / "spool")
    parent_spooler = MetricsSpooler(spool_dir, proc="spawncheck",
                                    registry=parent_reg, interval=0.0)
    parent_spooler.spool(force=True)
    out = str(tmp_path / "child.json")
    ctx = multiprocessing.get_context("spawn")
    p = ctx.Process(target=_spawn_probe, args=(out, spool_dir))
    p.start()
    p.join(timeout=120)
    assert p.exitcode == 0
    with open(out) as f:
        child = json.load(f)
    # spawn gives the child a FRESH interpreter: no inherited counts
    assert "tdl_spawn_parent_total" not in child["names_at_start"]
    # same proc label + same dir, different pid → structurally distinct files
    assert child["spool_path"] != parent_spooler.path
    assert os.path.exists(child["spool_path"])
    assert os.path.exists(parent_spooler.path)
    # and the merge keeps exactly one (the newest) for the shared proc label
    assert len(aggregate.read_spools(spool_dir)) == 1


# --------------------------------------------- wall-clock AST lint (sat 1)


def _dotted(node):
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        return f"{base}.{node.attr}" if base else node.attr
    return ""


def test_no_wall_clock_in_timing_paths():
    """Repo lint (ISSUE 7 satellite): ``time.time()`` steps backwards under
    NTP, so durations/deadlines must use ``time.perf_counter()`` /
    ``time.monotonic()``. Remaining ``time.time()`` sites are event
    timestamps and must say so with a ``# wallclock-ok:`` comment. Module
    aliases (``import time as _time``) are resolved per file so aliasing
    can't structurally bypass the lint."""
    root = ROOT / "deeplearning4j_tpu"
    offenders = []
    for path in sorted(root.rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        src = path.read_text()
        lines = src.splitlines()
        tree = ast.parse(src, filename=rel)
        time_aliases = {"time"} | {
            a.asname for node in ast.walk(tree) if isinstance(node, ast.Import)
            for a in node.names if a.name == "time" and a.asname}
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "time"
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in time_aliases
                    and "wallclock-ok" not in lines[node.lineno - 1]):
                offenders.append(f"{rel}:{node.lineno}")
    assert not offenders, (
        "time.time() in library code without a `# wallclock-ok:` "
        "justification (wall clock steps backwards under NTP — use "
        f"perf_counter/monotonic for anything timed): {offenders}")


# ----------------------------------------- bench telemetry check (sat 6)


def test_documented_bench_families_parse():
    import bench

    fams = bench.documented_bench_families()
    assert "tdl_step_phase_seconds" in fams
    assert "tdl_inference_batch_size" in fams
    assert "tdl_gang_restarts_total" not in fams  # marked "no": gangs don't run in bench
    assert all(f.startswith("tdl_") for f in fams)


def test_check_telemetry_flags_dead_families():
    import bench

    live_hist = {"type": "histogram", "series": [{"count": 3, "sum": 0.1}]}
    dead_hist = {"type": "histogram", "series": [{"count": 0, "sum": 0.0}]}
    drained_gauge = {"type": "gauge", "series": [{"labels": {}, "value": 0}]}
    out = {"telemetry": {"metrics": {"tdl_a": live_hist, "tdl_b": dead_hist,
                                     "tdl_c": drained_gauge}}}
    assert bench.check_telemetry(out, ["tdl_a", "tdl_c"]) == []
    # dead histogram, registered-but-unobserved, and absent all flag
    assert bench.check_telemetry(out, ["tdl_a", "tdl_b", "tdl_missing"]) == [
        "tdl_b", "tdl_missing"]


def test_documented_catalog_matches_declared_families():
    """Every `tdl_*` family string declared in library code must have a
    catalog row in docs/OBSERVABILITY.md — the doc stays the single source
    of truth as families are added."""
    doc = (ROOT / "docs" / "OBSERVABILITY.md").read_text()
    documented = set(re.findall(r"`(tdl_[a-z0-9_]+)`", doc))
    decl = re.compile(
        r'\.(?:counter|gauge|histogram)\(\s*["\'](tdl_[a-z0-9_]+)["\']')
    declared = set()
    for path in sorted((ROOT / "deeplearning4j_tpu").rglob("*.py")):
        declared.update(decl.findall(path.read_text()))
    assert len(declared) > 30  # the scan found the real declaration sites
    missing = declared - documented
    assert not missing, (
        f"metric families declared in code but missing from "
        f"docs/OBSERVABILITY.md's catalog: {sorted(missing)}")


# ------------------------------------------------------------- slow tier
# Real 2-process gangs under GangSupervisor (~30-60s each): the ISSUE 7
# acceptance runs. Slow-marked like the rest of the multiprocess tier.


@pytest.mark.slow
def test_aggregated_scrape_two_rank_gang_with_straggler(tmp_path):
    """Acceptance: one aggregated /metrics scrape shows the same family with
    distinct rank labels for both ranks, and an injected slow_ckpt_io on
    rank 1 surfaces as a nonzero straggler-skew gauge."""
    from deeplearning4j_tpu.parallel import GangSupervisor
    from deeplearning4j_tpu.ui.server import UIServer

    # 10 steps so the per-step 0.4s checkpoint sleep on rank 1 dominates the
    # (rank-symmetric) first-step compile inside the step-wall means
    env = {"TDL_MP_OUT": str(tmp_path / "out.json"),
           "TDL_MP_CKPT": str(tmp_path / "ckpt"),
           "TDL_MP_STEPS": "10",
           "TDL_MATMUL_PRECISION": "float32",
           "TDL_FAULT_SPEC": "slow_ckpt_io@value=0.4,rank=1",
           "TDL_METRICS_SPOOL_INTERVAL": "0",
           "TDL_FLIGHT_INTERVAL": "0"}
    os.makedirs(env["TDL_MP_CKPT"], exist_ok=True)
    reg = MetricsRegistry()
    sup = GangSupervisor(f"{WORKERS}:observability_train", n_processes=2,
                         n_local_devices=2, extra_env=env,
                         workdir=str(tmp_path / "gang"),
                         heartbeat_interval=0.0, startup_grace=300.0,
                         registry=reg)
    results = sup.run(timeout=540.0)
    for r in results:
        assert r.returncode == 0, f"rank {r.rank} failed:\n{r.stderr[-3000:]}"

    ui = UIServer(port=0)
    try:
        ui.attach_spool_dir(sup.spool_dir, local_proc="supervisor")
        url = f"http://127.0.0.1:{ui.port}/metrics"
        with urllib.request.urlopen(url, timeout=10) as r:
            text = r.read().decode()
    finally:
        ui.stop()
    parsed = _parse_prometheus(text)  # strict: a real scraper must accept it
    walls = parsed["tdl_step_wall_seconds_count"]
    per_rank = {dict(k).get("rank"): v for k, v in walls.items()}
    # superset: the scraping pytest process's own registry may contribute a
    # rank-less series when an earlier test ran a trainer (see the fast
    # merged-metrics test) — the gang's two spooled ranks are the assertion
    assert {"0", "1"} <= set(per_rank)
    assert all(v >= 2 for r, v in per_rank.items() if r in ("0", "1"))
    # rank 1 sleeps 0.4s in every checkpoint save → its iteration-to-
    # iteration wall dominates and the derived skew gauge is well over 1
    assert parsed["tdl_step_time_skew_ratio"][()] > 1.3
    assert parsed["tdl_step_time_slowest_rank"][()] == 1
    # per-rank means back the ratio up
    means = parsed["tdl_step_time_mean_seconds"]
    assert means[(("rank", "1"),)] > means[(("rank", "0"),)]


@pytest.mark.slow
def test_postmortem_from_crash_injected_gang(tmp_path):
    """Acceptance: a crash-injected supervised gang leaves a postmortem.json
    whose merged event stream is monotonically ordered and contains step
    events from every rank INCLUDING the crashed rank's final step."""
    from deeplearning4j_tpu.parallel import GangSupervisor

    env = {"TDL_MP_OUT": str(tmp_path / "out.json"),
           "TDL_MP_CKPT": str(tmp_path / "ckpt"),
           "TDL_MP_STEPS": "10",
           "TDL_MP_CKPT_EVERY": "2",
           "TDL_MATMUL_PRECISION": "float32",
           "TDL_FAULT_SPEC": "crash@iter=7,rank=1",
           "TDL_FLIGHT_INTERVAL": "0",
           "TDL_METRICS_SPOOL_INTERVAL": "0"}
    os.makedirs(env["TDL_MP_CKPT"], exist_ok=True)
    sup = GangSupervisor(f"{WORKERS}:supervised_train", n_processes=2,
                         n_local_devices=2, extra_env=env,
                         workdir=str(tmp_path / "gang"),
                         heartbeat_interval=0.0, startup_grace=300.0,
                         backoff_base=0.1, kill_grace=1.0, max_restarts=3,
                         registry=MetricsRegistry())
    results = sup.run(timeout=540.0)
    for r in results:
        assert r.returncode == 0, f"rank {r.rank} failed:\n{r.stderr[-3000:]}"
    assert sup.restarts >= 1

    assert os.path.exists(sup.postmortem_path)
    with open(sup.postmortem_path) as f:
        pm = json.load(f)
    assert pm["classification"] == "crash"
    assert 1 in pm["ranks"] and pm["iteration"] == 7
    ts = [e["t"] for e in pm["events"]]
    assert ts == sorted(ts)  # monotonic-clock-ordered merged stream
    assert {"rank0", "rank1", "supervisor"} <= set(pm["procs"])
    by_proc = {}
    for e in pm["events"]:
        by_proc.setdefault(e["proc"], []).append(e)
    # step events from every rank, including the victim's final step (the
    # step_begin at the crash iteration was flushed by the fault injector)
    for proc in ("rank0", "rank1"):
        assert any(e["kind"] == "step_begin" for e in by_proc[proc]), proc
    assert any(e["kind"] == "step_begin" and e.get("iteration") == 7
               for e in by_proc["rank1"])
    assert any(e["kind"] == "fault_injected" and e.get("fault") == "crash"
               for e in by_proc["rank1"])
    assert any(e["kind"] == "gang_failure" for e in by_proc["supervisor"])
    # checkpoint breadcrumbs made it too (save every 2 steps)
    assert any(e["kind"] == "ckpt_save" for e in pm["events"])
