"""Runner for ``kind: serve_family`` — ``serve``'s open-loop window, through
the same HTTP door, executor and ``PagedDecodeSlotPool``, for a configuration
whose file names its model ``family``: ``benchmark/models/<family>.py`` builds
the program's config, makes the weights and holds the comparison with
``benchmark/reference/<family>.py`` that decides ``correct``. With
``BENCHMARK_CHECK_CONTROL=<name>`` in the environment the run serves weights
with one fault of the family's making and ends after the check (exit 0 when
the check said ``correct: false``, as it must): the check's own control.

``run_window`` (which runs ``serve``'s ``traced_section``) and ``Client`` are
``serve``'s, unedited:
arrivals, what ``attempted`` / ``failed`` mean and how latency is taken are
defined there. Beside ``serve``'s observation this one holds ``family``: the
model's shapes, the pool's counters over exactly the traced section, and the
device seconds of every decode step inside it (the operations that started
between the end of the program before it and the end of its own
``kv.step.fetch``), for the readers that divide required work by
traced time.
"""

from __future__ import annotations

import glob
import http.client
import importlib
import json
import os
import shutil

import numpy as np

from benchmark import harness, loadgen, tracereduce
from benchmark.runners.serve import Client, run_window

STEP_ENDS, PROGRAM_ENDS = "kv.step.fetch", ("kv.step.fetch", "kv.prefill.fetch")
CONTROL_ENV = "BENCHMARK_CHECK_CONTROL"  # names a fault for the check to catch


def step_device_seconds(events):
    """Device-busy seconds of each decode step of a trace: chip 0's
    operations that START after the host saw the previous program end (the
    end of the last ``kv.step.fetch`` or ``kv.prefill.fetch`` before this
    step's own fetch began) and before this step's ``kv.step.fetch`` ended,
    their intervals merged. Both edges are ends of fetches because the
    device's clock runs about 0.7 ms behind the host's in a trace (PERF.md,
    PR 31): a step's first operations START before its ``kv.step.dispatch``
    span does. The first step of a trace has no edge before it and is left
    out."""
    fetches = sorted((e[2], e[2] + e[3]) for e in events
                     if e[0] == "host" and e[1] == STEP_ENDS)
    ends = np.sort([e[2] + e[3] for e in events
                    if e[0] == "host" and e[1] in PROGRAM_ENDS])
    ops = sorted((e[2], e[2] + e[3]) for e in events if e[0] == "device:0")
    starts = np.array([s for s, _ in ops])
    out = []
    for began, ended in fetches:
        before = np.searchsorted(ends, began) - 1
        if before < 0:
            continue
        lo, hi = np.searchsorted(starts, [ends[before], ended])
        busy, edge = 0.0, 0.0
        for s, e in ops[lo:hi]:
            busy += max(0.0, e - max(s, edge))
            edge = max(edge, e)
        if hi > lo:
            out.append(busy / 1e9)
    return out


def account(w: dict, spans: dict) -> dict:
    """Medians over the window's answered requests: the client's latency,
    every phase of the request's span, its steps and a step's two clocks."""
    rows = [(r, spans[r["id"]]) for r in w["records"]
            if r["in_window"] and r["ok"] and r["id"] in spans]
    if not rows:
        return {"requests": 0}
    def med(xs):
        return float(np.median(xs)) if len(xs) else None

    phases = {k: med([s["phases"][k] * 1e3 for _, s in rows if k in s.get("phases", {})])
              for k in rows[0][1].get("phases", {})}
    return {"requests": len(rows),
            "client_latency_ms": med([(r["done"] - r["sent"]) * 1e3 for r, _ in rows]),
            "phases_ms": phases, "steps": med([s.get("steps", 0) for _, s in rows]),
            "step_ms": med([x for _, s in rows for x in s.get("step_ms", [])]),
            "step_host_ms": med([x for _, s in rows for x in s.get("step_host_ms", [])])}


class FamilyTrace(harness.TracedWindow):
    """The traced section, with the pool's counters read at its two ends and
    the events kept long enough to find the decode steps in them."""

    def __init__(self, ctx, pool):
        super().__init__(ctx)
        self.pool = pool
        self.counters = None
        self.steps = None

    def start(self) -> None:
        super().start()
        self._before = self.pool.block_stats()

    def stop(self) -> None:
        # read first: stopping the profiler takes tens of seconds, and the
        # server goes on stepping meanwhile (2,461 steps counted beside 239
        # traced ones when this came second: PERF.md, PR 31)
        after = self.pool.block_stats()
        super().stop()
        self.counters = {k: after[k] - self._before[k] for k in after
                         if k.startswith(("moe_", "kv_blocks_"))}

    def reduce(self):
        files = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not files:
            return None
        events = tracereduce.load_events(files[0])
        self.steps = step_device_seconds(events)
        if self.ctx.dump_events:
            os.makedirs(os.path.dirname(os.path.abspath(self.ctx.dump_events)),
                        exist_ok=True)
            with open(self.ctx.dump_events, "w") as f:
                json.dump(events, f)
        shutil.rmtree(self.dir, ignore_errors=True)
        return tracereduce.reduce(events, n_devices=len(self.ctx.devices))


def run(ctx: harness.Context) -> dict:
    import jax

    from deeplearning4j_tpu.models.paged_decode import PagedDecodeSlotPool
    from deeplearning4j_tpu.monitoring import flight
    from deeplearning4j_tpu.serving.json_server import JsonModelServer

    t, clock = ctx.traffic, ctx.clock
    family = importlib.import_module(f"benchmark.models.{ctx.config['family']}")
    cfg = family.build_config(ctx.config, on_tpu=ctx.on_tpu,
                              max_len=int(t["max_len"]))
    rs = np.random.RandomState(ctx.seed % (2 ** 32))

    # the generator that is made for the chip: threefry draws 4.85 B values
    # in tens of seconds, and the weights are random either way
    key = jax.random.key(ctx.seed % (2 ** 32), impl="rbg")
    params = jax.block_until_ready(jax.jit(family.make_init(cfg))(key))
    clock.mark("weights")

    # a control run serves weights with one fault (the family's
    # ``control_params``) and ends with the check, which has to say so
    control = os.environ.get(CONTROL_ENV)
    served = family.control_params(params, control) if control else params
    pool = PagedDecodeSlotPool(served, cfg, slots=int(t["slots"]),
                               block_T=int(t["block_T"]),
                               max_len=int(t["max_len"]))
    checked = family.check_served_path(ctx, pool, cfg, served, rs,
                                       reference_params=params)
    clock.mark("check")
    if control:
        ctx.emit({"line": "control", "control": control, "correct": checked})
        raise SystemExit(int(checked))  # 0: the check caught the fault

    # every request's span, whole: the default ring keeps 512 events of all kinds
    recorder = flight.FlightRecorder(proc="benchmark", directory=None,
                                     capacity=1 << 20)
    flight.set_flight_recorder(recorder)
    warm_prompt = loadgen.prompt_tokens(rs, int(t["prompt_tokens"]["min"]),
                                        cfg.vocab_size)
    server = (JsonModelServer.Builder(None).generative(pool)
              .max_new_tokens(int(t["answer_tokens"]["max"]))
              .warmup_input(warm_prompt).deadline_ms(3_600_000).build())
    server.start()
    try:
        if not server.wait_ready(1800):
            raise RuntimeError("server never became ready")
        clock.mark("server_ready")
        # one request a prefill bucket the mix reaches, before the window:
        # nothing compiles inside it
        client = Client(server.port, server.endpoint, timeout=1800)
        buckets = sorted({pool.prompt_bucket(n) for n in range(
            int(t["prompt_tokens"]["min"]), int(t["prompt_tokens"]["max"]) + 1)})
        for b in buckets:
            n = min(b, int(t["prompt_tokens"]["max"]))
            body = json.dumps(loadgen.prompt_tokens(rs, n, cfg.vocab_size).tolist())
            status, _ = client.ask(f"warm-{b}", body.encode(), 2)
            if status != 200:
                raise RuntimeError(f"warm-up request for bucket {b}: HTTP {status}")
        clock.mark("warmup")
        ctx.emit({"line": "warm", "prefill_buckets": buckets,
                  "prefill_traces": pool.prefill_traces,
                  "decode_traces": pool.decode_traces})

        tracer = FamilyTrace(ctx, pool) if ctx.trace else None
        setup_s = clock.setup_s()  # to the first arrival of the pre-roll
        rates = ctx.sweep or [float(t["rate_rps"])]
        for rate in rates:
            w = run_window(ctx, server, pool, cfg, rs, rate, ctx.seconds,
                           tracer if rate == rates[-1] else None)
            ctx.emit({"line": "window", **{k: w[k] for k in (
                "rate_rps", "seconds", "attempted", "failed", "serve_tok_s",
                "offered_tok_s", "lat_p50", "lat_p90", "inflight_at_close",
                "queue_at_close")},
                "lateness_p99_ms": loadgen.percentile(w["lateness_ms"], 99)
                if w["lateness_ms"] else None,
                "rehearse": ctx.rehearse})
        conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=30)
        conn.request("GET", "/stats")
        stats = json.loads(conn.getresponse().read()).get("stats", {})
        conn.close()
    finally:
        server.stop(drain=False, timeout=10)
        flight.set_flight_recorder(None)

    spans = {e["request_id"]: e for e in recorder.events()
             if e.get("kind") == "request_span"}
    ctx.emit({"line": "requests", "columns": ["id", "due_s", "sent_s", "done_s",
                                              "status", "prompt", "answer"],
              "rows": [[r["id"], round(r["due"], 3), round(r["sent"], 3),
                        round(r["done"], 3), r["status"], r["prompt"], r["answer"]]
                       for r in w["records"] if r["in_window"]][:400]})
    ctx.emit({"line": "account", **account(w, spans)})
    reduced = tracer.reduce() if tracer else None
    if tracer:
        ctx.emit({"line": "family_trace", "steps_traced": len(tracer.steps or []),
                  "step_device_ms_median": float(np.median(tracer.steps)) * 1e3
                  if tracer.steps else None, "counters": tracer.counters})
    return {
        "correct": checked and ctx.counters.compiles_in_window == 0,
        "attempted": w["attempted"], "failed": w["failed"],
        "end_to_end": {"setup_s": setup_s, "serve_tok_s": w["serve_tok_s"],
                       "serve_lat_per_tok_p50_ms": w["lat_p50"],
                       "serve_lat_per_tok_p90_ms": w["lat_p90"]},
        "trace": reduced,
        "serve": {"window": w, "spans": spans, "executor_stats": stats},
        "family": {"shapes": family.shapes(cfg, slots=int(t["slots"]),
                                           block_T=int(t["block_T"])),
                   "traced_counters": tracer.counters if tracer else None,
                   "step_device_s": tracer.steps if tracer else None},
        "counters": ctx.counters.summary(),
        "memory": {"peak": harness.memory_peak_bytes(ctx.devices),
                   "limit": harness.memory_limit_bytes(ctx.devices)}
        if ctx.on_tpu else None,
        "peaks": ctx.peaks,
    }
