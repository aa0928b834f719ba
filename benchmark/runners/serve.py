"""Runner for ``kind: serve`` — open-loop requests through the HTTP door of
a ``JsonModelServer`` in generative mode over a ``PagedDecodeSlotPool``, in
the run's own process (one process per chip).

Arrivals start ``preroll_s`` before the window so that it opens on a steady
state. ``attempted`` is the requests DUE inside the window; latency is taken
over all of them, from the instant each was due to its full answer (the
server does not stream); a request not answered 200 with all its tokens by
``drain_s`` after the window is ``failed`` and counts as the worst latency.
``serve_tok_s`` counts prompt + generated tokens of requests answered inside
the window, over the window.
"""

from __future__ import annotations

import http.client
import json
import threading
import time

import numpy as np

from benchmark import harness, loadgen
from benchmark.models import transformer as family
from benchmark.reference import transformer as reference

WORST_MS_PER_TOKEN = 1e6  # what a failed request counts as, per token


def make_check(cfg, model: dict):
    """ONE jitted program: the reference's logits for the checked sequences,
    and the program's own full forward held to them. Tokens are arguments."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import forward

    def check(params, tokens):
        system = forward(params, tokens, cfg)
        with jax.default_matmul_precision("highest"):
            ref = reference.logits(params, reference.hidden(params, tokens, model),
                                   model)
        err = jnp.max(jnp.abs(system.astype(jnp.float32) - ref)) / jnp.max(jnp.abs(ref))
        return ref, err

    return jax.jit(check)


def check_served_path(ctx, pool, cfg, model, params, rs) -> bool:
    """Prefill then decode through the paged pool against the reference's
    full forward: two prompts, a few steps. Logits, not tokens, decide: with
    random weights the largest logit changes on rounding, so each token the
    pool chose must lie within ``argmax_gap_rtol`` x max|logit| of the
    reference's largest logit at that position (a wrong cache or position
    puts it several standard deviations below), and the program's full
    forward must match the reference's logits to ``logit_rtol``."""
    ck = ctx.traffic["check"]
    steps = int(ck["decode_steps"])
    width = pool.prompt_bucket(max(ck["prompt_lens"]) + steps + 1)
    rows, spans = [], []
    for n in ck["prompt_lens"]:
        prompt = loadgen.prompt_tokens(rs, int(n), cfg.vocab_size)
        slot, first = pool.admit(prompt, steps + 1)
        chosen = [int(first)]
        for _ in range(steps):
            out = pool.step()[slot]
            chosen.extend(int(x) for x in (out if isinstance(out, (list, tuple)) else [out]))
        pool.release(slot)
        chosen = chosen[:steps + 1]
        seq = np.zeros(width, np.int32)
        seq[:n] = prompt
        seq[n:n + steps] = chosen[:steps]  # teacher-forced with the pool's tokens
        rows.append(seq)
        spans.append((int(n), chosen))
    ref, fwd_err = make_check(cfg, model)(params, np.stack(rows))
    ref = np.asarray(ref)
    gaps = []
    for r, (n, chosen) in enumerate(spans):
        for j, tok in enumerate(chosen):  # token j was read at position n-1+j
            row = ref[r, n - 1 + j]
            gaps.append(float((row.max() - row[tok]) / np.abs(row).max()))
    fwd_err = float(fwd_err)
    ok = bool(max(gaps) <= ck["argmax_gap_rtol"] and fwd_err <= ck["logit_rtol"])
    ctx.emit({"line": "check", "served_tokens_checked": len(gaps),
              "argmax_gap_max": max(gaps), "argmax_gap_rtol": ck["argmax_gap_rtol"],
              "forward_logit_rel_err": fwd_err, "logit_rtol": ck["logit_rtol"],
              "correct": ok})
    return ok


class Client:
    """One request over the door; thread-per-request, started when due."""

    def __init__(self, port: int, endpoint: str, timeout: float):
        self.port, self.endpoint, self.timeout = port, endpoint, timeout

    def ask(self, rid: str, body: bytes, max_new: int):
        """(status, generated tokens or None)."""
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=self.timeout)
        try:
            conn.request("POST", self.endpoint, body=body, headers={
                "Content-Type": "application/json", "X-Request-Id": rid,
                "X-Max-New-Tokens": str(max_new)})
            resp = conn.getresponse()
            data = resp.read()
            if resp.status != 200:
                return resp.status, None
            return 200, json.loads(data)["output"]
        except (OSError, http.client.HTTPException, ValueError):
            return 0, None
        finally:
            conn.close()


def traced_section(tracer, seconds: float) -> None:
    tracer.start()
    with harness.annotate("bench:window"):
        time.sleep(max(0.5, seconds))
    tracer.stop()


def run_window(ctx, server, pool, cfg, rs, rate_rps, seconds, tracer):
    """One open-loop window at one rate. Returns the observation."""
    t = ctx.traffic
    reqs = loadgen.schedule(t, seed=ctx.seed, seconds=seconds, rate_rps=rate_rps)
    shared = loadgen.prompt_tokens(rs, int(t.get("shared_prefix_tokens", 0)),
                                   cfg.vocab_size)
    by_prompt = {}
    for r in reqs:
        if r.prompt_id not in by_prompt:
            by_prompt[r.prompt_id] = json.dumps(loadgen.prompt_tokens(
                rs, r.prompt_len, cfg.vocab_size, shared).tolist()).encode()
    bodies = [by_prompt[r.prompt_id] for r in reqs]
    client = Client(server.port, server.endpoint,
                    timeout=float(t["preroll_s"]) + seconds + float(t["drain_s"]) + 30)
    records = [None] * len(reqs)
    tag = f"w{int(rate_rps * 1000)}-"

    def send(r, t_open):
        due = t_open + r.due_s
        sent = time.perf_counter()
        status, out = client.ask(tag + str(r.index), bodies[r.index], r.answer_len)
        done = time.perf_counter()
        ok = status == 200 and out is not None and len(out) == r.answer_len
        records[r.index] = {"id": tag + str(r.index), "due": due - t_open,
                            "sent": sent - t_open, "done": done - t_open,
                            "status": status, "ok": ok,
                            "prompt": r.prompt_len, "answer": r.answer_len,
                            "in_window": r.in_window}

    occupancy, threads = [], []
    t_open = time.perf_counter() + float(t["preroll_s"]) + 0.05
    trace_on = float(t["trace_start_s"]) if tracer is not None else float("inf")
    tracing = None
    next_sample, i = 0.0, 0
    while True:
        now = time.perf_counter() - t_open
        if now >= seconds:
            break
        if now >= 0.0 and not occupancy:
            ctx.counters.open_window()
        if now >= trace_on:
            # on a thread of its own: stopping a trace takes seconds, and the
            # generator must not stall (it ran 8 s late when it did)
            tracing = threading.Thread(target=traced_section, args=(
                tracer, min(float(t["trace_seconds"]), seconds - now - 1.0)))
            tracing.start()
            trace_on = float("inf")
        if now >= next_sample:  # once a second of the window, from its opening
            b = pool.block_stats()
            occupancy.append(1.0 - b["blocks_free"] / max(1, b["blocks_total"]))
            next_sample = now + 1.0
        if i < len(reqs) and reqs[i].due_s <= now:
            th = threading.Thread(target=send, args=(reqs[i], t_open), daemon=True)
            th.start()
            threads.append(th)
            i += 1
            continue
        wake = min(seconds, next_sample, trace_on,
                   reqs[i].due_s if i < len(reqs) else seconds)
        time.sleep(max(0.0, min(wake - now, 0.05)))
    ctx.counters.close_window()
    inflight = sum(1 for th in threads if th.is_alive())
    if tracing is not None:
        tracing.join()
    deadline = time.perf_counter() + float(t["drain_s"])
    for th in threads:
        th.join(max(0.0, deadline - time.perf_counter()))

    # -- reduce ----------------------------------------------------------
    in_win = [r for r in reqs if r.in_window]
    lat_ms_tok, lateness_ms, failed = [], [], 0
    for r in in_win:
        rec = records[r.index]
        if rec is None or not rec["ok"]:
            failed += 1
            lat_ms_tok.append(WORST_MS_PER_TOKEN)
            continue
        lat_ms_tok.append((rec["done"] - rec["due"]) * 1e3 / rec["answer"])
        lateness_ms.append((rec["sent"] - rec["due"]) * 1e3)
    answered = [rec for rec in records
                if rec is not None and rec["ok"] and 0.0 <= rec["done"] < seconds]
    tokens_in_window = sum(rec["prompt"] + rec["answer"] for rec in answered)
    return {
        "rate_rps": rate_rps, "seconds": seconds,
        "attempted": len(in_win), "failed": failed,
        "serve_tok_s": tokens_in_window / seconds,
        "lat_p50": loadgen.percentile(lat_ms_tok, 50),
        "lat_p90": loadgen.percentile(lat_ms_tok, 90),
        "lateness_ms": lateness_ms,
        "records": [rec for rec in records if rec is not None],
        "block_occupancy": occupancy,
        "inflight_at_close": inflight,
        "queue_at_close": max(0, inflight - pool.slots),
        "offered_tok_s": sum(r.prompt_len + r.answer_len for r in in_win) / seconds,
    }


def run(ctx: harness.Context) -> dict:
    import jax

    from deeplearning4j_tpu.models.paged_decode import PagedDecodeSlotPool
    from deeplearning4j_tpu.monitoring import flight
    from deeplearning4j_tpu.serving.json_server import JsonModelServer

    t, clock = ctx.traffic, ctx.clock
    cfg = family.build_config(ctx.config, on_tpu=ctx.on_tpu, causal=True,
                              max_len=int(t["max_len"]))
    model = {**ctx.config["model"], "causal": True}
    rs = np.random.RandomState(ctx.seed % (2 ** 32))

    params = jax.block_until_ready(
        jax.jit(family.make_init(cfg))(jax.random.key(ctx.seed)))
    clock.mark("weights")

    pool = PagedDecodeSlotPool(params, cfg, slots=int(t["slots"]),
                               block_T=int(t["block_T"]),
                               max_len=int(t["max_len"]))
    checked = check_served_path(ctx, pool, cfg, model, params, rs)
    clock.mark("check")

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
        if not server.wait_ready(1200):
            raise RuntimeError("server never became ready")
        clock.mark("server_ready")
        # the server's own warm-up admits one prompt; the mix reaches more
        # prefill buckets, so send one request per bucket before the window
        client = Client(server.port, server.endpoint, timeout=1200)
        buckets = sorted({pool.prompt_bucket(n) for n in range(
            int(t["prompt_tokens"]["min"]), int(t["prompt_tokens"]["max"]) + 1)})
        for b in buckets:
            n = min(b, int(t["prompt_tokens"]["max"]))
            body = json.dumps(loadgen.prompt_tokens(rs, n, cfg.vocab_size).tolist())
            status, out = client.ask(f"warm-{b}", body.encode(), 2)
            if status != 200:
                raise RuntimeError(f"warm-up request for bucket {b}: HTTP {status}")
        clock.mark("warmup")
        ctx.emit({"line": "warm", "prefill_buckets": buckets,
                  "prefill_traces": pool.prefill_traces,
                  "decode_traces": pool.decode_traces})

        tracer = harness.TracedWindow(ctx) if ctx.trace else None
        setup_s = clock.setup_s()  # to the first arrival; the pre-roll is measurement, not set-up
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
    return {
        "correct": checked and ctx.counters.compiles_in_window == 0,
        "attempted": w["attempted"], "failed": w["failed"],
        "end_to_end": {"setup_s": setup_s, "serve_tok_s": w["serve_tok_s"],
                       "serve_lat_per_tok_p50_ms": w["lat_p50"],
                       "serve_lat_per_tok_p90_ms": w["lat_p90"]},
        "trace": tracer.reduce() if tracer else None,
        "serve": {"window": w, "spans": spans, "executor_stats": stats},
        "counters": ctx.counters.summary(),
        "memory": {"peak": harness.memory_peak_bytes(ctx.devices),
                   "limit": harness.memory_limit_bytes(ctx.devices)}
        if ctx.on_tpu else None,
        "peaks": ctx.peaks,
    }
