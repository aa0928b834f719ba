"""Production-hardened serving tests (ISSUE 5, SURVEY §2.6 S5/S7).

Covers the micro-batching executor (bounded admission, deadlines, coalescing
parity, graceful drain), the hardened JsonModelServer (429/504/413/503 +
Retry-After, /health vs /ready, restart robustness), the hardened
JsonModelClient (retry/backoff, circuit breaker, URLError normalization),
ParallelInference input validation, and the 32-client chaos stress test
driven by the ``slow_infer`` fault injector.
"""

import json
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.monitoring import MetricsRegistry
from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.parallel import ParallelInference
from deeplearning4j_tpu.serving import (BatchingInferenceExecutor,
                                        DeadlineExceededError,
                                        JsonModelClient, JsonModelServer,
                                        QueueFullError)


def _net():
    conf = (NeuralNetConfiguration.Builder().seed(7).updater(Adam(0.01)).list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


class SlowModel:
    """Deterministic stand-in: 2x the input after a fixed delay, counting
    calls and flagging when a forward has started."""

    def __init__(self, delay=0.0):
        self.delay = delay
        self.calls = 0
        self.started = threading.Event()

    def output(self, x):
        self.calls += 1
        self.started.set()
        if self.delay:
            time.sleep(self.delay)
        return np.asarray(x, np.float32) * 2.0


class FlakyModel(SlowModel):
    def __init__(self, fail_first=2):
        super().__init__()
        self.fail_first = fail_first

    def output(self, x):
        self.calls += 1
        self.started.set()
        if self.calls <= self.fail_first:
            raise RuntimeError("transient replica failure")
        return np.asarray(x, np.float32) * 2.0


def _counter_values(reg, name):
    m = reg.get(name)
    if m is None:
        return {}
    snap = m.snapshot()
    return {tuple(s["labels"].values()): s["value"] for s in snap["series"]}


def _wait_handlers_done(server):
    """A handler counts its request AFTER it has written the response: wait
    until none is in flight before reading ``tdl_inference_requests_total``."""
    with server._inflight_cv:
        assert server._inflight_cv.wait_for(lambda: server._inflight == 0, 10.0)


def _post(port, body, headers=None, timeout=15):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/predict", data=body,
        headers={"Content-Type": "application/json", **(headers or {})})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def _get(port, path, timeout=15):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout) as resp:
        return resp.status, json.loads(resp.read()), dict(resp.headers)


# ------------------------------------------------------- ParallelInference


def test_output_batched_empty_returns_empty():
    net = _net()
    pi = ParallelInference(net, batch_limit=8)
    assert pi.output_batched([]) == []


def test_output_batched_validates_mixed_requests():
    net = _net()
    pi = ParallelInference(net, batch_limit=8)
    ok = np.zeros((2, 4), np.float32)
    with pytest.raises(ValueError, match="request 1.*feature shape"):
        pi.output_batched([ok, np.zeros((2, 5), np.float32)])
    with pytest.raises(ValueError, match="request 2.*dtype"):
        pi.output_batched([ok, ok, np.zeros((2, 4), np.float64)])
    with pytest.raises(ValueError, match="request 0.*batch dimension"):
        pi.output_batched([np.float32(1.0)])


# --------------------------------------------------------------- executor


def test_executor_micro_batching_parity_and_coalescing(monkeypatch):
    """ISSUE 5 satellite: coalesced-batch outputs == per-request outputs to
    1e-6, and concurrent requests actually coalesce (fewer executor cycles
    than requests while a slow_infer fault holds the first cycle open)."""
    monkeypatch.setenv("TDL_FAULT_SPEC", "slow_infer@p=0.15")
    reg = MetricsRegistry()
    net = _net()
    pi = ParallelInference(net, batch_limit=8)
    ex = BatchingInferenceExecutor(parallel_inference=pi, max_queue=64,
                                   registry=reg).start()
    try:
        rs = np.random.RandomState(3)
        xs = [rs.randn(1 + i % 3, 4).astype(np.float32) for i in range(10)]
        expected = [net.output(x).numpy() for x in xs]
        futs = [ex.submit(x) for x in xs]
        for f in futs:
            assert f.wait(30.0)
            assert f.error is None
        for f, exp in zip(futs, expected):
            np.testing.assert_allclose(f.result, exp, atol=1e-6)
        cycles = reg.get("tdl_inference_batch_size").snapshot()["series"][0]
        assert 0 < cycles["count"] < 10  # coalesced, not one cycle per request
    finally:
        ex.stop(drain=True)


def test_executor_sheds_expired_requests_without_running_model():
    reg = MetricsRegistry()
    model = SlowModel(delay=0.3)
    ex = BatchingInferenceExecutor(model=model, max_queue=16,
                                   registry=reg).start()
    try:
        x = np.ones((1, 4), np.float32)
        f1 = ex.submit(x, deadline_ms=5000)
        assert model.started.wait(5.0)  # f1 is in the model now
        stale = [ex.submit(x, deadline_ms=50) for _ in range(4)]
        assert f1.wait(5.0) and f1.error is None
        for f in stale:
            assert f.wait(5.0)
            assert isinstance(f.error, DeadlineExceededError)
        # the expired requests never reached the model
        assert model.calls == 1
        shed = _counter_values(reg, "tdl_inference_shed_total")
        assert shed[("queue_expired",)] == 4
    finally:
        ex.stop(drain=True)


def test_executor_queue_full_and_graceful_drain():
    reg = MetricsRegistry()
    model = SlowModel(delay=0.3)
    ex = BatchingInferenceExecutor(model=model, max_queue=2,
                                   registry=reg).start()
    x = np.ones((1, 4), np.float32)
    f1 = ex.submit(x)
    assert model.started.wait(5.0)
    queued = [ex.submit(x), ex.submit(x)]
    with pytest.raises(QueueFullError):
        ex.submit(x)
    assert _counter_values(reg, "tdl_inference_shed_total")[("queue_full",)] == 1
    # graceful drain completes every accepted request
    ex.stop(drain=True)
    for f in [f1] + queued:
        assert f.done and f.error is None
        np.testing.assert_allclose(f.result, 2.0 * np.ones((1, 4)))


def test_executor_mixed_shape_requests_grouped_not_failed():
    """A mixed workload (different feature shapes in one cycle) is served by
    grouping, never a deep jax concatenate error."""
    ex = BatchingInferenceExecutor(model=SlowModel(), max_queue=16).start()
    try:
        fa = ex.submit(np.ones((2, 4), np.float32))
        fb = ex.submit(np.ones((1, 6), np.float32))
        assert fa.wait(5.0) and fb.wait(5.0)
        assert fa.error is None and fb.error is None
        assert fa.result.shape == (2, 4) and fb.result.shape == (1, 6)
    finally:
        ex.stop(drain=True)


# ----------------------------------------------------------------- server


def test_builder_parallel_inference_wiring_roundtrip():
    """ISSUE 5 satellite: DL4J builder parity — parallel_inference(pi) /
    batch_limit(n) route requests through the sharded bucketed forward."""
    net = _net()
    pi = ParallelInference(net, batch_limit=8)
    server = (JsonModelServer.Builder(net).port(0).parallel_inference(pi)
              .warmup_input(np.zeros((1, 4), np.float32)).build().start())
    try:
        assert server.wait_ready(30.0)
        client = JsonModelClient(port=server.port)
        x = np.random.RandomState(1).randn(3, 4).astype(np.float32)
        out = np.asarray(client.predict(x))
        np.testing.assert_allclose(out, net.output(x).numpy(), atol=1e-5)
    finally:
        server.stop()
    # batch_limit(n) without an explicit pi builds one internally
    server2 = JsonModelServer.Builder(net).port(0).batch_limit(8).build().start()
    try:
        assert server2.parallel_inference is not None
        out = np.asarray(JsonModelClient(port=server2.port).predict(x))
        np.testing.assert_allclose(out, net.output(x).numpy(), atol=1e-5)
    finally:
        server2.stop()


def test_server_deadline_header_yields_504_not_hang():
    reg = MetricsRegistry()
    server = JsonModelServer(SlowModel(delay=0.5), registry=reg).start()
    try:
        t0 = time.perf_counter()
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(server.port, b"[[1.0, 2.0, 3.0, 4.0]]",
                  headers={"X-Deadline-Ms": "100"})
        elapsed = time.perf_counter() - t0
        assert ei.value.code == 504
        assert elapsed < 0.45  # answered at the deadline, not after the model
        assert "deadline" in json.loads(ei.value.read())["error"]
        _wait_handlers_done(server)
        codes = _counter_values(reg, "tdl_inference_requests_total")
        assert codes[("504",)] == 1
    finally:
        server.stop()


def test_server_queue_full_429_with_retry_after():
    reg = MetricsRegistry()
    model = SlowModel(delay=0.5)
    server = JsonModelServer(model, max_queue=1, registry=reg).start()
    try:
        body = b"[[1.0, 2.0, 3.0, 4.0]]"
        results = []

        def fire():
            try:
                results.append(_post(server.port, body)[0])
            except urllib.error.HTTPError as e:
                results.append(e.code)

        t1 = threading.Thread(target=fire)
        t1.start()
        assert model.started.wait(5.0)  # first request is inside the model
        t2 = threading.Thread(target=fire)
        t2.start()
        time.sleep(0.1)  # second request now occupies the only queue slot
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(server.port, body)
        assert ei.value.code == 429
        assert ei.value.headers.get("Retry-After") is not None
        t1.join(10.0)
        t2.join(10.0)
        assert results == [200, 200]
        assert _counter_values(
            reg, "tdl_inference_shed_total")[("queue_full",)] == 1
    finally:
        server.stop()


def test_health_ready_split_and_graceful_drain():
    model = SlowModel(delay=0.4)
    server = JsonModelServer(
        model, warmup_input=np.zeros((1, 4), np.float32)).start()
    try:
        # /health is liveness: 200 even while the warmup forward runs
        assert _get(server.port, "/health")[0] == 200
        assert server.wait_ready(30.0)
        assert _get(server.port, "/ready")[0] == 200

        # an accepted slow request + concurrent shutdown: /ready flips 503
        # so balancers stop routing, and drain completes the request
        outcome = []

        def slow_request():
            outcome.append(_post(server.port, b"[[1.0, 2.0, 3.0, 4.0]]"))

        t = threading.Thread(target=slow_request)
        t.start()
        model.started.clear()
        assert model.started.wait(5.0)

        stopper = threading.Thread(target=lambda: server.stop(drain=True))
        stopper.start()
        saw_not_ready = False
        for _ in range(100):
            try:
                status, body, headers = _get(server.port, "/ready", timeout=2)
            except urllib.error.HTTPError as e:
                assert e.code == 503
                assert e.headers.get("Retry-After") is not None
                saw_not_ready = True
                break
            except (urllib.error.URLError, ConnectionError, OSError):
                break  # socket already closed — drain finished
            time.sleep(0.01)
        stopper.join(30.0)
        t.join(30.0)
        assert saw_not_ready
        assert outcome and outcome[0][0] == 200  # accepted request completed
        np.testing.assert_allclose(outcome[0][1]["output"],
                                   [[2.0, 4.0, 6.0, 8.0]])
    finally:
        server.stop()  # idempotent


def test_body_cap_413_and_missing_content_length():
    server = JsonModelServer(SlowModel(), max_body_bytes=1024).start()
    try:
        # ~7MB body: well past loopback socket buffers, so this also proves
        # the server DRAINS the oversized body before answering — otherwise
        # the close RSTs the upload and this surfaces as URLError, not 413
        big = json.dumps([[0.0] * 4] * 300_000).encode()
        assert len(big) > 4 << 20
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(server.port, big)
        assert ei.value.code == 413
        # a request with no Content-Length cannot be buffered safely → 413
        with socket.create_connection(("127.0.0.1", server.port),
                                      timeout=10) as s:
            s.sendall(b"POST /predict HTTP/1.1\r\nHost: localhost\r\n\r\n")
            chunks = []
            while True:
                chunk = s.recv(4096)
                if not chunk:
                    break
                chunks.append(chunk)
            reply = b"".join(chunks).decode()
        assert "413" in reply.split("\r\n")[0]
        assert "Content-Length header required" in reply
        # negative Content-Length must be rejected up front, never read(-1)
        with socket.create_connection(("127.0.0.1", server.port),
                                      timeout=10) as s:
            s.sendall(b"POST /predict HTTP/1.1\r\nHost: localhost\r\n"
                      b"Content-Length: -1\r\n\r\n")
            reply = s.recv(4096).decode()
        assert "400" in reply.split("\r\n")[0]
    finally:
        server.stop()


def test_server_restart_same_port_and_idempotent_stop():
    net = _net()
    server = JsonModelServer(net).start()
    port = server.port
    x = np.random.RandomState(2).randn(2, 4).astype(np.float32)
    ref = net.output(x).numpy()
    np.testing.assert_allclose(
        np.asarray(JsonModelClient(port=port).predict(x)), ref, atol=1e-5)
    server.stop()
    server.stop()  # idempotent: second stop is a no-op, not an error
    server.start()  # SO_REUSEADDR: rebinds the SAME port during TIME_WAIT
    try:
        assert server.port == port
        np.testing.assert_allclose(
            np.asarray(JsonModelClient(port=port).predict(x)), ref, atol=1e-5)
    finally:
        server.stop()


def test_fail_infer_fault_maps_to_500_then_recovers(monkeypatch):
    monkeypatch.setenv("TDL_FAULT_SPEC", "fail_infer@n=1")
    server = JsonModelServer(SlowModel()).start()
    try:
        client = JsonModelClient(port=server.port, retries=1,
                                 backoff_base=0.01, backoff_max=0.02)
        with pytest.raises(RuntimeError, match="500.*InjectedFault"):
            client.predict([[1.0, 2.0, 3.0, 4.0]])
        monkeypatch.setenv("TDL_FAULT_SPEC", "")  # fault cleared → recovery
        out = client.predict([[1.0, 2.0, 3.0, 4.0]])
        np.testing.assert_allclose(out, [[2.0, 4.0, 6.0, 8.0]])
    finally:
        server.stop()


# ----------------------------------------------------------------- client


def test_client_normalizes_connection_refused():
    with socket.socket() as s:  # grab a port that is certainly closed
        s.bind(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]
    client = JsonModelClient(port=dead_port, retries=0)
    with pytest.raises(RuntimeError) as ei:
        client.predict([[1.0]])
    assert client.url in str(ei.value)  # not a raw URLError escaping


def test_client_retries_converge_on_success():
    model = FlakyModel(fail_first=2)
    server = JsonModelServer(model).start()
    try:
        client = JsonModelClient(port=server.port, retries=4,
                                 backoff_base=0.01, backoff_max=0.05)
        out = client.predict([[1.0, 2.0, 3.0, 4.0]])
        np.testing.assert_allclose(out, [[2.0, 4.0, 6.0, 8.0]])
        assert model.calls == 3  # two 500s retried, third attempt lands
    finally:
        server.stop()


def test_client_never_retries_400():
    server = JsonModelServer(SlowModel()).start()
    try:
        client = JsonModelClient(port=server.port, retries=5,
                                 backoff_base=0.01)
        t0 = time.perf_counter()
        with pytest.raises(RuntimeError, match="400"):
            client.predict(["not", "numbers"])
        assert time.perf_counter() - t0 < 1.0  # no backoff loop happened
    finally:
        server.stop()


def test_client_circuit_breaker_opens_and_half_opens():
    class Boom:
        def __init__(self):
            self.calls = 0

        def output(self, x):
            self.calls += 1
            raise RuntimeError("replica wedged")

    model = Boom()
    server = JsonModelServer(model).start()
    try:
        client = JsonModelClient(port=server.port, retries=0,
                                 backoff_base=0.01, breaker_threshold=2,
                                 breaker_cooldown=0.2)
        for _ in range(2):
            with pytest.raises(RuntimeError, match="500"):
                client.predict([[1.0, 2.0, 3.0, 4.0]])
        assert model.calls == 2
        # breaker open: fails fast without touching the server
        with pytest.raises(RuntimeError, match="circuit breaker open"):
            client.predict([[1.0, 2.0, 3.0, 4.0]])
        assert model.calls == 2
        time.sleep(0.25)  # cooldown elapses → half-open probe goes through
        with pytest.raises(RuntimeError, match="500"):
            client.predict([[1.0, 2.0, 3.0, 4.0]])
        assert model.calls == 3
    finally:
        server.stop()


# ------------------------------------------------------------ chaos stress


def test_serving_chaos_32_clients(monkeypatch):
    """ISSUE 5 acceptance: slow_infer fault + 32 concurrent clients against a
    bounded queue — the server only ever answers 200/429/504, queue depth
    stays bounded, no client hangs, client retries converge on eventual 200s,
    and it is all visible in the tdl_inference_* metrics."""
    monkeypatch.setenv("TDL_FAULT_SPEC", "slow_infer@p=0.02")
    reg = MetricsRegistry()
    net = _net()
    server = (JsonModelServer.Builder(net).port(0).batch_limit(8)
              .queue_size(8).registry(reg)
              .warmup_input(np.zeros((1, 4), np.float32)).build().start())
    try:
        assert server.wait_ready(60.0)
        clients, per_client = 32, 3
        successes = [0] * clients
        depth_gauge = reg.get("tdl_inference_queue_depth")
        depth_samples = []
        stop_sampling = threading.Event()

        def sample_depth():
            while not stop_sampling.is_set():
                depth_samples.append(depth_gauge.value)
                time.sleep(0.005)

        def worker(idx):
            client = JsonModelClient(
                port=server.port, timeout=15, retries=12,
                backoff_base=0.01, backoff_max=0.1,
                breaker_threshold=10 ** 6, deadline_ms=10_000)
            x = np.random.RandomState(idx).randn(1, 4).astype(np.float32)
            for _ in range(per_client):
                client.predict(x)  # raises if retries don't converge
                successes[idx] += 1

        sampler = threading.Thread(target=sample_depth, daemon=True)
        sampler.start()
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
        stop_sampling.set()
        sampler.join(5.0)

        assert not any(t.is_alive() for t in threads)  # zero hung clients
        assert sum(successes) == clients * per_client  # retries converged
        assert max(depth_samples) <= 8  # admission queue stayed bounded

        codes = _counter_values(reg, "tdl_inference_requests_total")
        assert set(codes) <= {("200",), ("429",), ("504",)}
        assert ("500",) not in codes
        assert codes[("200",)] == clients * per_client
        snap = reg.snapshot()
        assert snap["tdl_inference_queue_wait_seconds"]["series"][0]["count"] > 0
        assert snap["tdl_inference_latency_seconds"]["series"][0]["count"] > 0
        assert snap["tdl_inference_batch_size"]["series"][0]["count"] > 0
        server.stop(drain=True)  # nothing in flight; drain is a clean no-op
    finally:
        server.stop()


# -------------------------------------------------- request IDs (ISSUE 10)


def test_request_id_echoed_on_success_and_generated_when_absent():
    model = SlowModel()
    server = JsonModelServer(model, registry=MetricsRegistry()).start()
    try:
        body = json.dumps([[1.0, 2.0, 3.0, 4.0]]).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/predict", data=body,
            headers={"Content-Type": "application/json",
                     "X-Request-Id": "client-abc-123"})
        with urllib.request.urlopen(req, timeout=15) as resp:
            assert resp.headers["X-Request-Id"] == "client-abc-123"
            out = json.loads(resp.read())
        assert out["request_id"] == "client-abc-123"
        # no client id → the server mints one and still echoes it
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/predict", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=15) as resp:
            rid = resp.headers["X-Request-Id"]
            out = json.loads(resp.read())
        assert rid and out["request_id"] == rid
    finally:
        server.stop()


def test_request_id_rides_error_responses_and_logs(caplog):
    import logging

    # 413 (body too big) and 429 (queue full) both carry the id in header
    # AND error JSON; the queue-full shed also logs it executor-side
    model = SlowModel(delay=0.6)
    server = JsonModelServer(model, max_queue=1, max_body_bytes=256,
                             registry=MetricsRegistry()).start()
    try:
        big = json.dumps([[0.0] * 2000]).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/predict", data=big,
            headers={"Content-Type": "application/json",
                     "X-Request-Id": "too-big-1"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=15)
        assert ei.value.code == 413
        assert ei.value.headers["X-Request-Id"] == "too-big-1"
        assert json.loads(ei.value.read())["request_id"] == "too-big-1"

        ok = json.dumps([[1.0, 2.0, 3.0, 4.0]]).encode()
        results = []

        def fire(rid):
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/predict", data=ok,
                headers={"Content-Type": "application/json",
                         "X-Request-Id": rid})
            try:
                with urllib.request.urlopen(req, timeout=30) as resp:
                    results.append((200, json.loads(resp.read())))
            except urllib.error.HTTPError as e:
                results.append((e.code, json.loads(e.read()),
                                e.headers.get("X-Request-Id")))

        with caplog.at_level(logging.DEBUG,
                             logger="deeplearning4j_tpu.serving"):
            # fill the 1-slot queue while the slow forward runs, then one
            # more request must be shed with 429 + its id echoed
            threads = [threading.Thread(target=fire, args=(f"rid-{i}",))
                       for i in range(6)]
            for t in threads:
                t.start()
                time.sleep(0.02)
            for t in threads:
                t.join(30.0)
        shed = [r for r in results if r[0] == 429]
        assert shed, f"no 429 among {[r[0] for r in results]}"
        code, body429, hdr = shed[0]
        assert body429["request_id"].startswith("rid-")
        assert hdr == body429["request_id"]
        assert any("admission queue full" in r.message and "rid-" in r.message
                   for r in caplog.records)
    finally:
        server.stop()


def test_request_id_sanitizes_garbage_header():
    from deeplearning4j_tpu.serving.json_server import _request_id

    assert _request_id("ok-id") == "ok-id"
    generated = _request_id("bad\nid")
    assert "\n" not in generated and len(generated) == 16
    assert len(_request_id("x" * 500)) == 16  # over-long → replaced
    assert len(_request_id(None)) == 16


# ------------------------------------------- request spans (ISSUE 11)


def _install_recorder():
    from deeplearning4j_tpu.monitoring import flight
    from deeplearning4j_tpu.monitoring.flight import FlightRecorder

    rec = FlightRecorder(proc="span-test", capacity=4096)
    flight.set_flight_recorder(rec)
    return rec


def _clear_recorder():
    from deeplearning4j_tpu.monitoring import flight

    flight.set_flight_recorder(None)


def _spans(rec, rid=None):
    """The request_span events of ``rid``. A completion's span is recorded
    AFTER its response is written, so a client that has just read the
    response waits a moment for it."""
    deadline = time.monotonic() + 5.0
    while True:
        found = [e for e in rec.events() if e["kind"] == "request_span"
                 and (rid is None or e.get("request_id") == rid)]
        if found or rid is None or time.monotonic() >= deadline:
            return found
        time.sleep(0.01)


def test_request_span_for_200_carries_full_phase_timeline():
    """ISSUE 11: a sampled 200's life — queue → batch_form → infer →
    serialize — reconstructs from ONE flight event joined by request id;
    ISSUE 28: with the door's own phases around them, tiling the span."""
    rec = _install_recorder()
    server = JsonModelServer(SlowModel(), registry=MetricsRegistry()).start()
    try:
        body = json.dumps([[1.0, 2.0, 3.0, 4.0]]).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/predict", data=body,
            headers={"Content-Type": "application/json",
                     "X-Request-Id": "span-ok-1"})
        with urllib.request.urlopen(req, timeout=15) as resp:
            assert resp.status == 200
        spans = _spans(rec, "span-ok-1")
        assert len(spans) == 1
        ev = spans[0]
        assert ev["outcome"] == "ok" and ev["code"] == 200
        assert list(ev["phases"]) == ["read", "parse", "queue", "batch_form",
                                      "infer", "handoff", "serialize", "write"]
        assert all(v >= 0 for v in ev["phases"].values())
        assert sum(ev["phases"].values()) == pytest.approx(
            ev["t_end"] - ev["t_start"], abs=1e-6)
        assert ev["batch_rows"] >= 1
    finally:
        server.stop()
        _clear_recorder()


def test_request_span_for_shed_queue_full_and_expired_deadline():
    """ISSUE 11 satellite: a 429 and an expired-in-queue 504 leave spans
    too (outcome=shed_queue_full / shed_deadline) — an error's timeline is
    as reconstructable as a 200's."""
    rec = _install_recorder()
    model = SlowModel(delay=0.4)
    server = JsonModelServer(model, max_queue=1,
                             registry=MetricsRegistry()).start()
    try:
        ok = json.dumps([[1.0, 2.0, 3.0, 4.0]]).encode()

        def fire(rid, deadline_ms=None):
            headers = {"Content-Type": "application/json",
                       "X-Request-Id": rid}
            if deadline_ms:
                headers["X-Deadline-Ms"] = str(deadline_ms)
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/predict", data=ok,
                headers=headers)
            try:
                with urllib.request.urlopen(req, timeout=30) as resp:
                    return resp.status
            except urllib.error.HTTPError as e:
                e.read()
                return e.code

        t1 = threading.Thread(target=fire, args=("span-first",))
        t1.start()
        assert model.started.wait(5.0)  # first request inside the model
        # occupies the single queue slot, with a deadline shorter than the
        # in-flight forward → expires IN QUEUE
        t2 = threading.Thread(target=fire, args=("span-expired", 100))
        t2.start()
        time.sleep(0.1)
        # queue full now → shed at admission
        assert fire("span-full") == 429
        t1.join(30.0)
        t2.join(30.0)
        server.stop(drain=True)

        full = _spans(rec, "span-full")
        assert len(full) == 1 and full[0]["outcome"] == "shed_queue_full"
        expired = _spans(rec, "span-expired")
        assert len(expired) == 1
        assert expired[0]["outcome"] == "shed_deadline"
        assert expired[0]["phases"]["queue"] >= 0.1  # its life WAS the queue
        ok_span = _spans(rec, "span-first")
        assert len(ok_span) == 1 and ok_span[0]["outcome"] == "ok"
    finally:
        server.stop()
        _clear_recorder()


def test_span_sampling_is_deterministic_by_request_id():
    from deeplearning4j_tpu.monitoring import flight
    from deeplearning4j_tpu.monitoring.flight import FlightRecorder
    from deeplearning4j_tpu.serving.executor import span_sampled

    # inactive flight recording → never sampled (hot path pays one lookup)
    assert not span_sampled("abc", 1)
    rec = FlightRecorder(proc="sample-test")
    flight.set_flight_recorder(rec)
    try:
        assert span_sampled("abc", 1)
        assert span_sampled(None, 1)
        # deterministic: same id, same verdict, every call
        verdicts = {rid: span_sampled(rid, 4) for rid in
                    (f"r{i}" for i in range(64))}
        assert verdicts == {rid: span_sampled(rid, 4) for rid in verdicts}
        kept = sum(verdicts.values())
        assert 0 < kept < 64  # ~1/4 sampled
        assert not span_sampled(None, 4)  # no id → no joinable timeline
    finally:
        flight.set_flight_recorder(None)


# ------------------------------------------- client metrics (ISSUE 11)


def test_client_metrics_record_outcomes_and_retries():
    reg = MetricsRegistry()
    model = FlakyModel(fail_first=2)
    server = JsonModelServer(model).start()
    try:
        client = JsonModelClient(port=server.port, retries=4,
                                 backoff_base=0.01, backoff_max=0.05,
                                 registry=reg)
        client.predict([[1.0, 2.0, 3.0, 4.0]])  # two 500s then success
        hist = reg.get("tdl_client_request_seconds").snapshot()["series"]
        by_outcome = {s["labels"]["outcome"]: s["count"] for s in hist}
        assert by_outcome == {"ok": 1}  # ONE request from the caller's view
        retries = _counter_values(reg, "tdl_client_retries_total")
        assert retries[("http_500",)] == 2

        with pytest.raises(RuntimeError, match="400"):
            client.predict(["not", "numbers"])
        by_outcome = {s["labels"]["outcome"]: s["count"]
                      for s in reg.get("tdl_client_request_seconds")
                      .snapshot()["series"]}
        assert by_outcome == {"ok": 1, "bad_request": 1}
    finally:
        server.stop()


def test_client_metrics_connection_and_breaker_outcomes():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]
    reg = MetricsRegistry()
    client = JsonModelClient(port=dead_port, retries=0, breaker_threshold=1,
                             breaker_cooldown=30.0, registry=reg)
    with pytest.raises(RuntimeError):
        client.predict([[1.0]])
    with pytest.raises(RuntimeError, match="circuit breaker open"):
        client.predict([[1.0]])
    by_outcome = {s["labels"]["outcome"]: s["count"]
                  for s in reg.get("tdl_client_request_seconds")
                  .snapshot()["series"]}
    assert by_outcome == {"connection": 1, "breaker_open": 1}
