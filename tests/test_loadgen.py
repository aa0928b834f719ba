"""Trace-replay load generator (ISSUE 11 tentpole, layer 4).

Fast tier: deterministic arrival schedules, diurnal/burst rate shaping,
deadline mix, spec JSON round-trip, and a short live replay report.

Slow tier: the ISSUE 11 acceptance — a seeded diurnal + 10x-burst replay
against a live JsonModelServer (32-client harness) with a history ring, SLO
tracker and alert engine evaluating DURING the replay: the windowed p99 and
burn-rate rules fire under the burst and clear after recovery (matching
alert/alert_clear intervals), a sampled 200 and a shed 504 each reconstruct
their span timeline by request id, and the steady phase fires nothing.
"""

import json
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.monitoring import MetricsRegistry
from deeplearning4j_tpu.serving import (Burst, JsonModelServer, LoadGenerator,
                                        TraceSpec)


class EchoModel:
    """2x the input, optionally with a per-ROW cost so overload builds real
    queues: capacity is ~1/row_cost rows/sec, which a burst can exceed."""

    def __init__(self, row_cost_s: float = 0.0):
        self.row_cost_s = row_cost_s

    def output(self, x):
        x = np.asarray(x, np.float32)
        if self.row_cost_s:
            time.sleep(self.row_cost_s * x.shape[0])
        return x * 2.0


# ------------------------------------------------------------------- spec


def test_spec_validation():
    with pytest.raises(ValueError, match="must be > 0"):
        TraceSpec(duration_s=0)
    with pytest.raises(ValueError, match="amplitude"):
        TraceSpec(diurnal_amplitude=1.0)
    with pytest.raises(ValueError, match="positive weights"):
        TraceSpec(deadline_mix=((0.0, None),))


def test_arrivals_deterministic_and_json_roundtrip():
    spec = TraceSpec(duration_s=5.0, base_rate=80, seed=42,
                     diurnal_amplitude=0.5, bursts=(Burst(2.0, 1.0, 8.0),),
                     deadline_mix=((0.8, None), (0.2, 100.0)))
    a, b = spec.arrivals(), spec.arrivals()
    assert a == b  # same seed → byte-identical schedule
    assert TraceSpec(duration_s=5.0, base_rate=80, seed=43,
                     diurnal_amplitude=0.5, bursts=(Burst(2.0, 1.0, 8.0),),
                     deadline_mix=((0.8, None), (0.2, 100.0))).arrivals() != a
    rt = TraceSpec.from_dict(json.loads(json.dumps(spec.to_dict())))
    assert rt == spec and rt.arrivals() == a
    # every arrival inside the trace, deadline drawn from the mix
    assert all(0 <= t < 5.0 for t, _ in a)
    assert {d for _, d in a} <= {None, 100.0}
    with_deadline = sum(1 for _, d in a if d is not None)
    assert 0.1 < with_deadline / len(a) < 0.35  # ~20% by weight


def test_rate_curve_diurnal_and_burst_shape():
    spec = TraceSpec(duration_s=10.0, base_rate=100, seed=1,
                     diurnal_amplitude=0.5, bursts=(Burst(6.0, 2.0, 10.0),))
    # diurnal: starts at the trough (phase -pi/2) → rate_at(0) = base*(1-amp)
    assert spec.rate_at(0.0) == pytest.approx(50.0)
    assert spec.rate_at(5.0) == pytest.approx(150.0)  # peak mid-trace
    assert spec.rate_at(6.5) / spec.rate_at(5.9) > 8  # 10x burst edge
    assert spec.peak_rate == pytest.approx(1500.0)
    arrivals = spec.arrivals()
    in_burst = sum(1 for t, _ in arrivals if 6.0 <= t < 8.0)
    pre_burst = sum(1 for t, _ in arrivals if 3.0 <= t < 5.0)
    assert in_burst / max(1, pre_burst) > 4  # the spike is in the schedule


def test_live_replay_report_shape():
    server = JsonModelServer(EchoModel(),
                             warmup_input=np.zeros((1, 2), np.float32)).start()
    try:
        assert server.wait_ready(30.0)
        spec = TraceSpec(duration_s=1.5, base_rate=40, seed=3)
        rep = LoadGenerator(spec, server.port, n_clients=4,
                            payload=[[1.0, 2.0]], slo_threshold_ms=500,
                            slo_target=0.99, record_requests=True).run()
        assert rep["offered"] == len(spec.arrivals())
        assert rep["outcomes"].get("200", 0) == rep["offered"]
        assert rep["slo"]["attainment"] == 1.0
        assert rep["slo"]["error_budget_remaining"] == 1.0
        assert rep["slo"]["burn_rate_overall"] == 0.0
        assert rep["latency_ms"]["p99"] is not None
        assert len(rep["requests"]) == rep["offered"]
        # request ids are deterministic → joinable across runs/spans
        assert rep["requests"][0]["request_id"].startswith("replay-3-")
        # open-loop fidelity: the generator kept to its schedule
        assert rep["lateness_ms"]["p99"] < 500
    finally:
        server.stop()


# ------------------------------------------------------------- slow tier


@pytest.mark.slow
def test_replay_acceptance_burst_fires_and_clears_windowed_alerts():
    """ISSUE 11 acceptance: seeded diurnal+burst replay against a live
    server (32-client chaos-harness scale) → SLO report with attainment /
    budget / burn; p99+burn rules fire during the 10x burst and record
    matching alert/alert_clear intervals; a sampled 200 and a shed 504
    reconstruct full span timelines by request id; nothing fires in the
    steady pre-burst phase."""
    from deeplearning4j_tpu.monitoring import (AlertEngine, HistoryRing,
                                               SloTracker, default_objectives,
                                               default_rules, flight,
                                               get_registry)
    from deeplearning4j_tpu.monitoring.flight import FlightRecorder
    from deeplearning4j_tpu.parallel.supervisor import _alert_intervals

    rec = FlightRecorder(proc="replay-test", capacity=16384)
    flight.set_flight_recorder(rec)
    reg = MetricsRegistry()
    # per-row cost 10ms → capacity ~100 rows/s; steady ~40-50/s is
    # comfortable (measured steady p99 ~35ms), the 10x burst (~500/s
    # offered) is not — queues build, latency climbs past the deadline
    # slice: exactly the regime the windowed rules must catch
    server = JsonModelServer(EchoModel(row_cost_s=0.01), max_queue=256,
                             registry=reg,
                             warmup_input=np.zeros((1, 2), np.float32)).start()
    try:
        assert server.wait_ready(60.0)
        dur = 12.0
        burst = Burst(5.0, 3.0, 10.0)
        spec = TraceSpec(duration_s=dur, base_rate=40.0, seed=11,
                         diurnal_amplitude=0.3, bursts=(burst,),
                         deadline_mix=((0.8, None), (0.2, 150.0)))
        threshold_s = 0.1
        ring = HistoryRing(registry=reg, interval=0.0, capacity=1024)
        tracker = SloTracker(
            default_objectives(latency_threshold_s=threshold_s,
                               target=0.95, window_s=2.0),
            history_view=ring, registry=reg,
            burn_windows=(("fast", 2.0), ("slow", 8.0)))
        rules = default_rules(p99_latency_s=threshold_s,
                              latency_window_s=2.0,
                              burn_fast=3.0, burn_slow=1.5,
                              shed_window_s=2.0)
        engine = AlertEngine(rules, registry=reg, history_view=ring)
        t0 = time.monotonic()
        edges = []  # (monotonic t, rule, kind) from live evaluation
        stop_eval = threading.Event()

        def evaluate_loop():
            while not stop_eval.is_set():
                ring.sample(force=True)
                tracker.evaluate()
                engine.evaluate()
                stop_eval.wait(0.2)

        evaluator = threading.Thread(target=evaluate_loop, daemon=True)
        evaluator.start()
        report = LoadGenerator(
            spec, server.port, n_clients=32, payload=[[1.0, 2.0]],
            slo_threshold_ms=threshold_s * 1e3, slo_target=0.95,
            record_requests=True).run()
        # keep evaluating through recovery so firing rules can CLEAR
        # (windowed values fall back under threshold once the burst drains)
        recovery_deadline = time.monotonic() + 20.0
        while time.monotonic() < recovery_deadline:
            if not any(a["firing"] for a in engine.evaluate()):
                break
            time.sleep(0.2)
        stop_eval.set()
        evaluator.join(10.0)
        server.stop(drain=True)

        # -- the SLO report is machine-readable and shows the damage ------
        slo = report["slo"]
        assert slo["attainment"] is not None and slo["attainment"] < 1.0
        assert slo["error_budget_remaining"] < 1.0
        assert slo["burn_rate_worst_window"] > 1.0  # the burst burned hot
        outcomes = report["outcomes"]
        assert outcomes.get("200", 0) > 0
        assert set(outcomes) <= {"200", "429", "504"}  # only clean sheds

        # -- the windowed rules fired during the burst, then cleared ------
        alert_events = [e for e in rec.events()
                        if e["kind"] in ("alert", "alert_clear")]
        fired_rules = {e["rule"] for e in alert_events if e["kind"] == "alert"}
        assert "p99_latency_rising" in fired_rules
        assert ("error_budget_burn_fast" in fired_rules
                or "error_budget_burn_slow" in fired_rules)
        # steady phase clean: every rise happened at/after the burst began
        rise_offsets = [e["t"] - t0 for e in alert_events
                        if e["kind"] == "alert"]
        assert min(rise_offsets) >= burst.start_s - 0.5
        # intervals pair up: the p99 rule rose and CLEARED (postmortem form)
        intervals = _alert_intervals(sorted(alert_events,
                                            key=lambda e: e["t"]))
        p99_rows = [r for r in intervals if r["rule"] == "p99_latency_rising"]
        assert p99_rows and any(not r["still_firing"] for r in p99_rows)
        closed = [r for r in p99_rows if not r["still_firing"]][0]
        assert closed["duration"] > 0

        # -- span timelines reconstruct by request id ---------------------
        spans = {e["request_id"]: e for e in rec.events()
                 if e["kind"] == "request_span"}
        ok_rows = [r for r in report["requests"] if r["outcome"] == "200"
                   and r["request_id"] in spans]
        assert ok_rows, "no sampled 200 with a span event"
        ok_span = spans[ok_rows[0]["request_id"]]
        assert ok_span["outcome"] == "ok"
        assert set(ok_span["phases"]) == {"read", "parse", "queue",
                                          "batch_form", "infer", "handoff",
                                          "serialize", "write"}
        shed_rows = [r for r in report["requests"] if r["outcome"] == "504"
                     and r["request_id"] in spans]
        assert shed_rows, "no shed 504 with a span event"
        shed_span = spans[shed_rows[0]["request_id"]]
        assert shed_span["outcome"] == "shed_deadline"
        assert shed_span["phases"]["queue"] > 0  # its life was the queue
    finally:
        server.stop()
        flight.set_flight_recorder(None)
