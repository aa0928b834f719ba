"""Benchmark runner — prints ONE JSON line covering all 5 BASELINE configs.

Headline metric (BASELINE.json): ResNet-50 images/sec/chip. The other four
configs (LeNet MNIST TTA, GravesLSTM chars/sec, Word2Vec words/sec, BERT
tokens/sec) ride in the ``configs`` key of the same line.

Every train step is ONE compiled XLA executable; the loops below keep
dispatch async and sync once at the end. The mixed-precision policy
(TDL_MATMUL_PRECISION; see deeplearning4j_tpu/common/precision.py) is
recorded alongside each number per BASELINE.md's measurement protocol.

No reference numbers exist to compare against (BASELINE.json "published" is
empty), so vs_baseline is the ratio against this repo's own previous round,
read from the per-backend BENCH_BASELINE.<backend>.json. A stored baseline is
only comparable when its measurement config (batch / image size / effective
matmul precision) matches the current run (ADVICE r2); an off-config run
reports vs_baseline=1.0, and no run writes a baseline file into the checkout.

The header names the device as jax reports it (``platform``, ``device_kind``,
``device_count``); a config whose children ran elsewhere carries its own
``platform``. The process exits non-zero when any config reports ``error``.
ROADMAP S1 rebuilds this file into the ledger's instrument; until then
``chip_smoke.py`` is the proof that the main paths run on the chip.
"""

from __future__ import annotations

import json
import os
import pathlib
import sys
import time

import numpy as np

from deeplearning4j_tpu.common.environment import host_cpu_count

_HERE = pathlib.Path(__file__).parent


# ---------------------------------------------------------------- calibration


def calibration_probe():
    """Pinned probe timed alongside every config (VERDICT r3 weak #3): two
    fixed reference measurements taken in the SAME window as each config, so
    a later run can separate code changes from machine-window changes:

    - ``probe_ms``: 8-deep 2048^2 bf16 matmul chain (~0.55 TFLOP), compute-
      shaped — scales with the window's achievable device throughput.
    - ``sync_ms``: scalar device fetch — the per-sync round-trip latency.
    """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def chain(x):
        for _ in range(8):
            x = (x @ x) * 1e-3 + x
        return x

    a = jnp.full((2048, 2048), 0.001, jnp.bfloat16)
    out = chain(a)          # compile
    float(jnp.sum(out[:1, :1]))
    t0 = time.perf_counter()
    n = 5
    for _ in range(n):
        out = chain(out)
    float(jnp.sum(out[:1, :1]))
    probe_ms = (time.perf_counter() - t0) / n * 1e3

    t0 = time.perf_counter()
    float(jnp.asarray(0.0) + 1.0)
    sync_ms = (time.perf_counter() - t0) * 1e3
    return {"probe_ms": round(probe_ms, 2), "sync_ms": round(sync_ms, 2),
            "probe_shape": "8x(2048^2 bf16 matmul)"}


# ------------------------------------------------------- cost observatory


def _roofline_probe():
    """Measured achievable matmul flops/sec in THIS window (ISSUE 10): a
    pinned matmul chain at the effective compute dtype. The utilization a
    config reports is achieved-model-flops over THIS number — a measured
    roofline, so the ratio stays honest across backends (a vendor
    peak-TFLOPs constant would be fiction on the CPU smoke)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.common.precision import compute_dtype

    n = 1024
    reps = 4

    @jax.jit
    def chain(x):
        for _ in range(reps):
            x = (x @ x) * 1e-3 + x
        return x

    a = jnp.full((n, n), 0.5, compute_dtype())
    chain(a).block_until_ready()  # compile outside the window
    k = 3
    t0 = time.perf_counter()
    for _ in range(k):
        a = chain(a)
    a.block_until_ready()
    dt = time.perf_counter() - t0
    return 2.0 * n ** 3 * reps * k / dt


def _utilization(flops_per_step, steps, window_s, roofline):
    achieved = flops_per_step * steps / window_s if window_s > 0 else 0.0
    return {"achieved_gflops_per_sec": round(achieved / 1e9, 2),
            "roofline_gflops_per_sec": round(roofline / 1e9, 2),
            "utilization": round(achieved / roofline, 4) if roofline else None}


def _trim_table(table, top=12):
    """Bench-JSON-sized view of a cost table: the top-N layers by flops plus
    one 'others' roll-up row (ResNet-50 has ~120 rows; the gauges carry the
    full set, the JSON line stays readable)."""
    layers = sorted(table["layers"], key=lambda r: -r["flops"])
    if len(layers) > top:
        rest = layers[top:]
        layers = layers[:top] + [{
            "layer": f"(+{len(rest)} more)", "kind": "others",
            "flops": sum(r["flops"] for r in rest),
            "param_bytes": sum(r["param_bytes"] for r in rest),
            "activation_bytes": sum(r["activation_bytes"] for r in rest),
            "pct": round(sum(r["pct"] for r in rest), 2)}]
    return {**table, "layers": layers}


# ----------------------------------------------------------- step attribution


def _phase_recorder():
    """Per-step phase breakdown (ISSUE 7 layer 3) on the PROCESS registry, so
    the `tdl_step_phase_seconds` histograms ride the telemetry block and the
    per-variant percentage tables come from the same observations."""
    from deeplearning4j_tpu.monitoring import StepPhaseRecorder

    return StepPhaseRecorder()


# --------------------------------------------------------------------- config


def _scale(on_tpu):
    """(resnet, lenet, lstm, w2v, bert) shape params; small on CPU smoke."""
    if on_tpu:
        return {
            # steps=40: the closing sync amortizes over a long window
            "resnet50": dict(batch=256, hw=224, classes=1000, steps=40, warmup=3, pipeline_steps=3),
            "lenet": dict(batch=128, examples=12800, target_acc=0.95, max_epochs=12),
            "lstm": dict(batch=64, vocab=77, seqlen=200, tbptt=50, steps=30, warmup=3),
            "w2v": dict(sent=20000, layer=100, batch=16384),
            # steps=40: same amortization rationale as resnet
            "bert": dict(batch=16, seq=128, steps=40, warmup=3, tiny=False),
            "serving": dict(clients=16, requests=320, batch_limit=16,
                            features=64, classes=8, queue=256),
            "serving_slo": dict(duration_s=20.0, base_rate=120.0, clients=32,
                                burst_mult=10.0, batch_limit=16, features=64,
                                classes=8, queue=256, slo_threshold_ms=250.0,
                                slo_target=0.99),
            "bert_large_fsdp": dict(batch=8, seq=128, steps=8, warmup=2,
                                    large=True, tp=1),
            "pipeline_parallel": dict(stages=4, layers=12, seq=128,
                                      microbatch=4, m1=4, m2=8, steps=8,
                                      warmup=2, fwd_repeats=5,
                                      force_devices=4),
            "serving_pool": dict(slots=8, duration_s=12.0, base_rate=60.0,
                                 burst_mult=10.0, max_new=16, clients=48,
                                 max_new_mix=(4, 8, 16, 48),
                                 d_model=256, n_layers=4, n_heads=8,
                                 d_ff=1024, vocab=8192, max_len=256,
                                 queue=256, replicas=2,
                                 pool_duration_s=8.0, pool_rate=30.0,
                                 slo_threshold_ms=1000.0, slo_target=0.99),
            "reshard": dict(features=64, hidden=512, classes=8, steps=4,
                            replicas=2),
            "ckpt_lineage": dict(features=256, hidden=2048, classes=32,
                                 steps=3, saves=4),
            # gangs run platform="cpu" regardless of backend: the sweep
            # prices fleet orchestration, not device math
            "hpo": dict(trials=8, rungs=(4, 8), concurrent=4, seed=7,
                        resume_trials=3, etl_images=48, etl_iters=3),
            "deploy": dict(features=256, hidden=2048, classes=32, steps=3,
                           canary_requests=2000),
            "trace_overhead": dict(clients=8, requests_per_round=320,
                                   rounds=3, batch_limit=16, features=64,
                                   classes=8, queue=256, train_steps=30,
                                   train_batch=256, train_features=256,
                                   train_hidden=512),
            # few requests x long generations packed into a burst: the
            # replay measures decode DRAIN speed, not the arrival schedule
            "paged_decode": dict(d_model=256, n_layers=6, n_heads=8,
                                 d_ff=1024, vocab=8192, max_len=512,
                                 block_T=32, slots_dense=4, paged_slots=32,
                                 short_len=40, cap_prefix_len=224,
                                 cap_suffix_len=16, cap_max_new=16,
                                 max_new=384, draft_layers=1, spec_tokens=5,
                                 duration_s=0.3, base_rate=110.0, clients=32,
                                 prefix_tenants=4, prefix_len=96,
                                 suffix_len=16, queue=512),
        }
    return {
        "resnet50": dict(batch=8, hw=64, classes=10, steps=5, warmup=2, pipeline_steps=3),
        "lenet": dict(batch=64, examples=1280, target_acc=0.90, max_epochs=6),
        "lstm": dict(batch=8, vocab=32, seqlen=100, tbptt=50, steps=3, warmup=1),
        "w2v": dict(sent=400, layer=32, batch=2048),
        "bert": dict(batch=2, seq=64, steps=3, warmup=1, tiny=True),
        "serving": dict(clients=4, requests=80, batch_limit=8,
                        features=16, classes=4, queue=64),
        "serving_slo": dict(duration_s=6.0, base_rate=40.0, clients=8,
                            burst_mult=6.0, batch_limit=8, features=16,
                            classes=4, queue=64, slo_threshold_ms=250.0,
                            slo_target=0.99),
        "bert_large_fsdp": dict(batch=2, seq=64, steps=2, warmup=1,
                                large=False, tp=1),
        "pipeline_parallel": dict(stages=2, layers=6, seq=32, microbatch=2,
                                  m1=4, m2=8, steps=2, warmup=1,
                                  fwd_repeats=3, force_devices=4),
        "serving_pool": dict(slots=4, duration_s=5.0, base_rate=24.0,
                             burst_mult=6.0, max_new=8, clients=24,
                             max_new_mix=(2, 4, 8, 24),
                             d_model=64, n_layers=2, n_heads=4, d_ff=128,
                             vocab=256, max_len=64, queue=128, replicas=2,
                             pool_duration_s=4.0, pool_rate=12.0,
                             slo_threshold_ms=2000.0, slo_target=0.95),
        "reshard": dict(features=16, hidden=32, classes=4, steps=2,
                        replicas=2),
        "ckpt_lineage": dict(features=32, hidden=256, classes=8, steps=2,
                             saves=3),
        "hpo": dict(trials=4, rungs=(2, 4), concurrent=4, seed=7,
                    resume_trials=3, etl_images=32, etl_iters=2),
        "deploy": dict(features=32, hidden=256, classes=8, steps=2,
                       canary_requests=400),
        "trace_overhead": dict(clients=4, requests_per_round=80, rounds=2,
                               batch_limit=8, features=16, classes=4,
                               queue=64, train_steps=6, train_batch=32,
                               train_features=32, train_hidden=64),
        # few requests x long generations packed into a burst: the replay
        # measures decode DRAIN speed, not the arrival schedule or prefill
        "paged_decode": dict(d_model=64, n_layers=6, n_heads=4, d_ff=128,
                             vocab=256, max_len=256, block_T=16,
                             slots_dense=2, paged_slots=16,
                             short_len=24, cap_prefix_len=112,
                             cap_suffix_len=8, cap_max_new=8, max_new=192,
                             draft_layers=1, spec_tokens=7,
                             duration_s=0.2, base_rate=60.0, clients=16,
                             prefix_tenants=2, prefix_len=48, suffix_len=8,
                             queue=256),
    }


# ------------------------------------------------------------------ resnet-50


def bench_resnet50(p):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import ResNet50

    batch, hw, classes = p["batch"], p["hw"], p["classes"]
    net = ResNet50(num_classes=classes, input_shape=(3, hw, hw)).init()
    step = net._train_step_fn()

    rs = np.random.RandomState(0)
    x = {"input": jnp.asarray(rs.rand(batch, 3, hw, hw).astype(np.float32))}
    y = {"output": jnp.asarray(np.eye(classes, dtype=np.float32)[rs.randint(0, classes, batch)])}
    rng = jax.random.key(0)
    it = jnp.asarray(0, jnp.int32)
    ep = jnp.asarray(0, jnp.int32)

    params, opt, bn = net.params_, net.updater_state, net.bn_state
    for _ in range(p["warmup"]):
        params, opt, bn, loss = step(params, opt, bn, it, ep, x, y, None, rng)
    float(loss)  # device fetch = the host waits for every queued step

    phases = _phase_recorder()
    t0 = time.perf_counter()
    for _ in range(p["steps"]):
        with phases.phase("compute"):
            params, opt, bn, loss = step(params, opt, bn, it, ep, x, y, None, rng)
        phases.step_done()
    float(loss)
    dt = time.perf_counter() - t0
    out = {"metric": "resnet50_train_images_per_sec",
           "value": round(batch * p["steps"] / dt, 2),
           "unit": "images/sec/chip", "batch": batch, "image_size": hw}

    # ISSUE 10: per-layer cost attribution + achieved-vs-roofline. Estimator
    # only — re-lowering ResNet-50 for cost_analysis would double the
    # config's compile bill; LeNet/BERT carry the XLA-validated tables
    from deeplearning4j_tpu.monitoring import costmodel

    table = costmodel.publish("resnet50", costmodel.layer_costs(net, batch))
    out["cost"] = {**_trim_table(table),
                   **_utilization(table["total_flops"], p["steps"], dt,
                                  _roofline_probe())}

    # real-input-pipeline variant (SURVEY §2.3 D3 / VERDICT r2 missing #3):
    # JPEGs on disk → ImageRecordReader decode+augment → async prefetch;
    # proves ETL doesn't bottleneck the step (target ≥90% of synthetic)
    pipe_steps = p.get("pipeline_steps", 0)
    if pipe_steps:
        out["pipeline"] = _resnet_pipeline_variant(
            p, step, params, opt, bn, rng, out["value"], pipe_steps)
    return out


def _pad_labels_iter(base, classes, n_cls):
    """Pad dir-derived one-hot labels out to the model's class count ON THE
    HOST, before device staging — doing it consumer-side would read a device-
    resident label array back to host every step (the d2h→h2d round trip the
    device pipeline exists to remove)."""
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import DataSetIterator

    class _Pad(DataSetIterator):
        def has_next(self):
            return base.has_next()

        def reset(self):
            base.reset()

        def batch(self):
            return base.batch()

        def next(self):
            ds = base.next()
            y = np.zeros((ds.features.shape[0], classes), np.float32)
            y[:, :n_cls] = ds.labels[:, :min(n_cls, classes)]
            return DataSet(ds.features, y)

    return _Pad()


def _make_u8_step(step, ingest):
    """Fuse the on-device ingest (uint8 NHWC wire → f32 NCHW normalized) in
    front of the synthetic train step — ONE executable, normalization runs
    next to the matmuls."""
    import jax

    def step_u8(params, opt, bn, it, ep, xu8, y, rng):
        return step(params, opt, bn, it, ep, {"input": ingest(xu8)},
                    {"output": y}, None, rng)

    return jax.jit(step_u8, donate_argnums=(0, 1, 2))


def _resnet_pipeline_variant(p, step, params, opt, bn, rng, synthetic_ips, steps):
    """Device-resident JPEG path (ISSUE 4): decode+augment host-side on the
    persistent thread pool, uint8 NHWC over the wire (4x fewer h2d bytes),
    DevicePrefetchIterator staging the next batches to HBM while the current
    step runs, cast/scale/NCHW fused into the compiled step."""
    import shutil
    import tempfile

    import jax.numpy as jnp
    from PIL import Image

    from deeplearning4j_tpu.data import (
        DevicePrefetchIterator,
        FlipImageTransform,
        ImagePreProcessingScaler,
        ImageRecordReader,
        ImageRecordReaderDataSetIterator,
        ParentPathLabelGenerator,
        PipelineImageTransform,
        RandomCropTransform,
        make_device_ingest,
    )
    from deeplearning4j_tpu.data.records import FileSplit
    from deeplearning4j_tpu.monitoring import MetricsRegistry

    batch, hw, classes = p["batch"], p["hw"], p["classes"]
    n_images = batch * (steps + 1)
    tmp = tempfile.mkdtemp(prefix="bench_imgs_")
    try:
        rs = np.random.RandomState(0)
        src = hw + 32
        for i in range(n_images):
            cls = i % min(classes, 16)
            d = os.path.join(tmp, f"c{cls:03d}")
            os.makedirs(d, exist_ok=True)
            Image.fromarray(rs.randint(0, 255, (src, src, 3), dtype=np.uint8)).save(
                os.path.join(d, f"i{i}.jpg"), quality=85)
        chain = PipelineImageTransform([
            RandomCropTransform(hw, hw), FlipImageTransform(1)])
        rr = ImageRecordReader(hw, hw, 3, ParentPathLabelGenerator(),
                               transform=chain, uint8_wire=True)
        rr.initialize(FileSplit(tmp))
        n_cls = rr.num_labels()
        it_j = jnp.asarray(0, jnp.int32)
        ep_j = jnp.asarray(0, jnp.int32)
        # fresh registry: per-variant h2d/input-wait numbers (the process
        # registry would mix this variant's counters with the cached one's)
        data = DevicePrefetchIterator(
            _pad_labels_iter(ImageRecordReaderDataSetIterator(
                rr, batch, num_workers=min(16, host_cpu_count())),
                classes, n_cls),
            buffer_size=3, registry=MetricsRegistry())
        jstep = _make_u8_step(step, make_device_ingest(
            ImagePreProcessingScaler(), source_layout="NHWC"))
        done = 0
        t0 = None
        phases = _phase_recorder()
        while data.has_next() and done <= steps:
            with phases.phase("input"):
                ds = data.next()  # already device-resident uint8 NHWC
            if ds.features.shape[0] < batch:
                break
            with phases.phase("compute"):
                params, opt, bn, loss = jstep(params, opt, bn, it_j, ep_j,
                                              ds.features, ds.labels, rng)
            done += 1
            if t0 is None:  # first batch is warmup (compile + queue fill):
                # discard its phases entirely — observing the compile outlier
                # would skew the exported tdl_step_phase_seconds histogram
                phases.discard()
                float(loss)
                t0 = time.perf_counter()
            else:
                phases.step_done()
        float(loss)
        dt = time.perf_counter() - t0
        ips = batch * (done - 1) / dt
        pipe_stats = data.stats()
        data.reset()  # stop the worker + release the staged HBM batches
        jpeg = {"images_per_sec": round(ips, 2),
                # ISSUE 7 layer 3: where does a step's wall actually go —
                # input (blocked on the prefetcher), compute (step dispatch),
                # h2d/collective (≈0 here: staging overlaps worker-side,
                # single chip). Percentages of measured step wall, ~100 total
                "phases": phases.summary(),
                "vs_synthetic": round(ips / synthetic_ips, 3), "steps": done - 1,
                # JPEG decode is host-CPU-bound (~3ms/core/image at 224²):
                # the AFFINITY core count (not os.cpu_count — a cgroup-
                # limited host has fewer) is the ceiling for THIS path; the
                # cached + multi-process etl paths below are the answer on
                # small hosts
                "host_cpus": host_cpu_count(),
                # h2d MB/s measured on the real staged batches + consumer
                # input-wait per step (≈0 when prefetch keeps the chip fed)
                **pipe_stats}
        # each variant's steps DONATE the state buffers — thread the live
        # (params, opt, bn) from one variant into the next
        cached, params, opt, bn = _resnet_pipeline_cached(
            p, jstep, params, opt, bn, rng, synthetic_ips, steps, tmp)
        etl = _resnet_pipeline_etl(
            p, jstep, params, opt, bn, rng, synthetic_ips, steps, tmp)
        return {**jpeg, "cached": cached, "etl": etl}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _resnet_pipeline_cached(p, jstep, params, opt, bn, rng, synthetic_ips,
                            steps, img_dir):
    """Pre-decoded uint8 cache path (VERDICT r3 #3): decode once → memmap →
    vectorized crop/flip on the fly → uint8 NHWC staged to device by the
    prefetcher, cast/scale/NCHW on-chip. Proves the ETL overlap machinery on
    a 1-core host. ``jstep`` is the jpeg variant's already-compiled
    uint8-ingest step — a fresh `_make_u8_step` closure here would miss
    jax's jit cache and retrace ResNet-50 a second time."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.data import (
        CachedImageDataSetIterator,
        DevicePrefetchIterator,
        PreDecodedImageCache,
    )
    from deeplearning4j_tpu.data.records import FileSplit
    from deeplearning4j_tpu.monitoring import MetricsRegistry

    batch, hw, classes = p["batch"], p["hw"], p["classes"]
    t0 = time.perf_counter()
    cache = PreDecodedImageCache(os.path.join(img_dir, "_u8cache"),
                                 (hw + 32, hw + 32)).build(
        FileSplit(img_dir), num_workers=min(16, host_cpu_count()))
    build_s = time.perf_counter() - t0
    n_cls = cache.num_labels()

    data = DevicePrefetchIterator(
        _pad_labels_iter(CachedImageDataSetIterator(
            cache, batch, crop=(hw, hw), dtype=np.uint8), classes, n_cls),
        buffer_size=3, registry=MetricsRegistry())
    it_j = jnp.asarray(0, jnp.int32)
    ep_j = jnp.asarray(0, jnp.int32)
    done = 0
    t0 = None
    loss = None
    phases = _phase_recorder()
    while done <= steps:
        if not data.has_next():
            data.reset()
        with phases.phase("input"):
            ds = data.next()
        if ds.features.shape[0] < batch:
            continue
        with phases.phase("compute"):
            params, opt, bn, loss = jstep(params, opt, bn, it_j, ep_j,
                                          ds.features, ds.labels, rng)
        done += 1
        if t0 is None:  # first batch warms compile + queue: discard its
            # phases (the compile outlier must not skew the histogram)
            phases.discard()
            float(loss)
            t0 = time.perf_counter()
        else:
            phases.step_done()
    float(loss)
    dt = time.perf_counter() - t0
    ips = batch * (done - 1) / dt
    pipe_stats = data.stats()
    data.reset()  # stop the worker + release the staged HBM batches

    # host-only ETL rate (no device): proves whether the input machinery or
    # the host→device link is the binding constraint
    host_it = CachedImageDataSetIterator(cache, batch, crop=(hw, hw), dtype=np.uint8)
    list(host_it)  # warm page cache
    t0 = time.perf_counter()
    cnt = 0
    for _ in range(2):
        host_it.reset()
        for ds in host_it:
            cnt += ds.features.shape[0]
    host_ips = cnt / (time.perf_counter() - t0)

    # raw H2D bandwidth of one uint8 batch through whatever link exists
    # (PCIe on a TPU host). Warm both the transfer
    # and block_until_ready so the timed window holds only the copy — a
    # compile or sync round trip in-window would bias the number low.
    blob = np.zeros((batch, hw, hw, 3), np.uint8)
    blob2 = np.ones_like(blob)  # distinct buffer: defeats transfer caching
    jnp.asarray(blob).block_until_ready()
    t0 = time.perf_counter()
    jnp.asarray(blob2).block_until_ready()
    h2d_s = time.perf_counter() - t0
    h2d_mbps = blob.nbytes / 1e6 / h2d_s

    return ({"images_per_sec": round(ips, 2),
             "vs_synthetic": round(ips / synthetic_ips, 3),
             "phases": phases.summary(),
             "steps": done - 1, "cache_build_s": round(build_s, 2),
             "host_etl_images_per_sec": round(host_ips, 1),
             "host_etl_vs_synthetic": round(host_ips / synthetic_ips, 3),
             # measured on the real staged batches (stats) + the isolated
             # single-blob probe, to tell pipeline overhead from raw link b/w
             **pipe_stats,
             "h2d_probe_MBps": round(h2d_mbps, 1)},
            params, opt, bn)  # live post-donation state for the next variant


def _resnet_pipeline_etl(p, jstep, params, opt, bn, rng, synthetic_ips,
                         steps, img_dir):
    """Multi-process sharded ETL path (ISSUE 6): N worker PROCESSES decode/
    augment into a shared-memory ring (true host parallelism past the GIL),
    zero-copy views staged to device by the prefetcher, decoded-batch cache
    making epoch ≥2 decode-free. Reports the worker-count SCALING CURVE
    (host-only consumption rate per worker count, steady-state = cache-warm)
    plus the full train-loop throughput at the largest worker count."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.data import (
        DevicePrefetchIterator,
        EtlDataSetIterator,
        ImageEtlSpec,
    )
    from deeplearning4j_tpu.monitoring import MetricsRegistry

    batch, hw, classes = p["batch"], p["hw"], p["classes"]
    spec = ImageEtlSpec.from_directory(
        img_dir, hw, hw, batch_size=batch, num_classes=classes,
        store_pad=32, cache_dir=os.path.join(img_dir, "_etlcache"))

    from deeplearning4j_tpu.monitoring import get_registry

    def host_rate(workers, epochs=2):
        it = EtlDataSetIterator(spec, num_workers=workers,
                                registry=MetricsRegistry())
        try:
            for _ in it:  # warmup epoch: spawn amortized, cache populated
                continue
            t0 = time.perf_counter()
            n = 0
            for _ in range(epochs):
                it.reset()
                while it.has_next():
                    n += it.next().features.shape[0]
            return n / (time.perf_counter() - t0)
        finally:
            it.close()

    host = host_cpu_count()
    curve = [{"workers": w, "host_images_per_sec": round(host_rate(w), 1)}
             for w in sorted({1, 2, 4, host})]

    # full stack at the largest worker count: decode → ring → device_put →
    # fused uint8 ingest train step. PROCESS registry on purpose (unlike the
    # per-variant fresh registries above): this variant is what makes the
    # tdl_h2d_*/tdl_etl_*/prefetch families show up in the telemetry block,
    # so --check-telemetry can prove they're alive end to end
    w_max = curve[-1]["workers"]
    data = DevicePrefetchIterator(
        EtlDataSetIterator(spec, num_workers=w_max, registry=get_registry()),
        buffer_size=3, registry=get_registry())
    it_j = jnp.asarray(0, jnp.int32)
    ep_j = jnp.asarray(0, jnp.int32)
    done = 0
    t0 = None
    loss = None
    phases = _phase_recorder()
    try:
        while done <= steps:
            if not data.has_next():
                data.reset()
            with phases.phase("input"):
                ds = data.next()
            with phases.phase("compute"):
                params, opt, bn, loss = jstep(params, opt, bn, it_j, ep_j,
                                              ds.features, ds.labels, rng)
            done += 1
            if t0 is None:  # first batch warms compile + ring fill: discard
                # its phases (the compile outlier must not skew the histogram)
                phases.discard()
                float(loss)
                t0 = time.perf_counter()
            else:
                phases.step_done()
        float(loss)
        dt = time.perf_counter() - t0
        pipe_stats = data.stats()  # includes the merged etl_* counters
    finally:
        data.close()
    ips = batch * (done - 1) / dt
    return {"workers_curve": curve, "workers": w_max,
            "images_per_sec": round(ips, 2),
            "vs_synthetic": round(ips / synthetic_ips, 3),
            "phases": phases.summary(),
            "steps": done - 1, **pipe_stats}


# --------------------------------------------------------------- lenet (TTA)


def _lenet_cost(net, batch):
    """ISSUE 10: per-layer cost table for LeNet joined against XLA
    cost_analysis of the compiled train step, plus the live-HBM breakdown —
    publishes tdl_model_flops_per_step / tdl_hbm_peak_bytes /
    tdl_layer_cost_info / tdl_hbm_bytes on the process registry."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.monitoring import costmodel

    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.rand(batch, 1, 28, 28).astype(np.float32))
    y = jnp.asarray(np.eye(10, dtype=np.float32)[rs.randint(0, 10, batch)])
    xla = costmodel.xla_step_cost(
        net._train_step_fn(), net.params_, net.updater_state, net.bn_state,
        jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32), x, y,
        None, None, jax.random.key(0))
    table = costmodel.publish("lenet", costmodel.layer_costs(net, batch), xla)
    table["hbm"] = costmodel.net_hbm_breakdown(net, model="lenet")
    return table


def bench_lenet(p):
    from deeplearning4j_tpu.data.datasets import MnistDataSetIterator
    from deeplearning4j_tpu.models import LeNet

    net = LeNet(num_classes=10).init()
    train_it = MnistDataSetIterator(p["batch"], train=True, num_examples=p["examples"])
    test_it = MnistDataSetIterator(256, train=False, num_examples=min(2560, p["examples"]))

    t0 = time.perf_counter()
    tta = None
    images = 0
    train_time = 0.0  # ADVICE r2: exclude evaluate() from the throughput denominator
    for epoch in range(p["max_epochs"]):
        train_it.reset()
        te = time.perf_counter()
        for ds in train_it:
            net.fit(ds)
            images += ds.features.shape[0]
        train_time += time.perf_counter() - te
        test_it.reset()
        acc = net.evaluate(test_it).accuracy()
        if acc >= p["target_acc"]:
            tta = time.perf_counter() - t0
            break
    return {"metric": "lenet_mnist_time_to_accuracy",
            "value": round(tta, 2) if tta is not None else None,  # null = not reached (valid JSON)
            "unit": f"sec_to_{p['target_acc']:.0%}_acc",
            "reached": tta is not None, "final_acc": round(float(acc), 4),
            "synthetic": bool(getattr(train_it, "synthetic", False)),
            "images_per_sec": round(images / train_time, 1),
            # ISSUE 10: where the step's flops/bytes go, validated against
            # XLA's own count of the compiled executable ("coverage")
            "cost": _lenet_cost(net, p["batch"])}


# -------------------------------------------------------- graveslstm char-rnn


def bench_lstm(p):
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.models import TextGenerationLSTM
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    B, V, T = p["batch"], p["vocab"], p["seqlen"]
    net = MultiLayerNetwork(TextGenerationLSTM(vocab_size=V, tbptt_length=p["tbptt"]).conf()).init()
    rs = np.random.RandomState(0)
    idx = rs.randint(0, V, (B, T))
    x = np.eye(V, dtype=np.float32)[idx].transpose(0, 2, 1)  # [B,V,T]
    y = np.eye(V, dtype=np.float32)[np.roll(idx, -1, 1)].transpose(0, 2, 1)

    import jax
    import jax.numpy as jnp

    # device-resident batch: this config times the tbptt step, not the
    # re-upload of the same ~8MB batch on every fit
    xd, yd = jnp.asarray(x), jnp.asarray(y)
    jax.block_until_ready((xd, yd))
    ds = DataSet(xd, yd)

    def _sync():
        # a scalar fetch of the newest params waits for every queued fit
        return float(jax.tree.leaves(net.params_)[0].ravel()[0])

    for _ in range(p["warmup"]):
        net.fit(ds)
    _sync()
    t0 = time.perf_counter()
    for _ in range(p["steps"]):
        net.fit(ds)
    _sync()
    dt = time.perf_counter() - t0
    return {"metric": "graveslstm_chars_per_sec",
            "value": round(B * T * p["steps"] / dt, 1),
            "unit": "chars/sec", "batch": B, "seqlen": T, "tbptt": p["tbptt"]}


# ------------------------------------------------------------------- word2vec


def bench_w2v(p):
    from deeplearning4j_tpu.nlp.word2vec import Word2Vec

    rs = np.random.RandomState(0)
    vocab = [f"w{i}" for i in range(2000)]
    zipf = 1.0 / np.arange(1, len(vocab) + 1)
    zipf /= zipf.sum()
    sentences = [" ".join(rs.choice(vocab, size=rs.randint(8, 20), p=zipf))
                 for _ in range(p["sent"])]
    total_words = sum(len(s.split()) for s in sentences)

    w2v = Word2Vec(layer_size=p["layer"], window=5, negative=5, epochs=1,
                   batch_size=p.get("batch", 1024))
    # warmup fit compiles the step executables (same vocab + static batch →
    # cache hit on the timed fit); steady-state throughput is the metric
    w2v.fit(sentences)
    t0 = time.perf_counter()
    w2v.fit(sentences)
    dt = time.perf_counter() - t0
    return {"metric": "word2vec_words_per_sec",
            "value": round(total_words / dt, 1), "unit": "words/sec",
            "corpus_words": total_words, "layer_size": p["layer"],
            "batch_size": p.get("batch", 1024)}


# ----------------------------------------------------------------- bert mlm


def bench_bert(p):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import TransformerConfig, init_params, make_train_step
    from deeplearning4j_tpu.nn.updaters import Adam

    B, T = p["batch"], p["seq"]
    cfg = (TransformerConfig.tiny(dropout=0.0) if p["tiny"]
           else TransformerConfig.bert_base(max_len=T, dropout=0.0))
    params = init_params(jax.random.key(0), cfg)
    updater = Adam(1e-4)
    opt = updater.init(params)
    step = jax.jit(make_train_step(cfg, updater), donate_argnums=(0, 1))

    rs = np.random.RandomState(0)
    # TF-BERT pretraining layout: the MLM head runs only at masked_lm_positions
    # (~15% of T) — the D×V tied decoder is the step's biggest matmul, so the
    # gather cuts it ~T/P× (VERDICT r4 weak #3 attack, with the bf16+fp32-acc
    # projection in models/transformer.mlm_head).
    P = max(1, int(T * 0.15))
    positions = np.stack([np.sort(rs.choice(T, P, replace=False)) for _ in range(B)])
    batch = {
        "tokens": jnp.asarray(rs.randint(0, cfg.vocab_size, (B, T)), jnp.int32),
        "mlm_positions": jnp.asarray(positions, jnp.int32),
        "labels": jnp.asarray(rs.randint(0, cfg.vocab_size, (B, P)), jnp.int32),
        "weights": jnp.ones((B, P), jnp.float32),
    }
    rng = jax.random.key(1)
    it = jnp.asarray(0, jnp.int32)

    def timed(run, b):
        """ONE measurement protocol for all three variants: warmup runs,
        true-sync, timed window, true-sync. ``run(b)`` advances its own
        captured state and returns the step loss."""
        for _ in range(p["warmup"]):
            loss = run(b)
        float(loss)
        t0 = time.perf_counter()
        for _ in range(p["steps"]):
            loss = run(b)
        float(loss)
        return time.perf_counter() - t0

    state = {"params": params, "opt": opt}
    del params, opt  # donated into the step from here on — no other refs

    def run_mlm(b):
        state["params"], state["opt"], loss = step(state["params"],
                                                   state["opt"], b, it, rng)
        return loss

    dt = timed(run_mlm, batch)

    # ISSUE 10: the functional transformer's cost table, validated against
    # XLA cost_analysis of the compiled MLM step, + roofline utilization of
    # the timed window above
    from deeplearning4j_tpu.models.transformer import layer_costs
    from deeplearning4j_tpu.monitoring import costmodel

    xla_cost = costmodel.xla_step_cost(step, state["params"], state["opt"],
                                       batch, it, rng)
    cost = costmodel.publish("transformer",
                             layer_costs(cfg, B, T, mlm_positions=P), xla_cost)
    cost.update(_utilization(xla_cost["flops"] or cost["total_flops"],
                             p["steps"], dt, _roofline_probe()))

    # masked variant: padding mask present → the Pallas masked-flash path
    # (r4 silently fell back to the O(T^2) dense path under any mask)
    pad = np.ones((B, T), np.float32)
    pad[:, int(T * 0.9):] = 0.0
    dt_masked = timed(run_mlm, {**batch, "pad_mask": jnp.asarray(pad)})

    # SQuAD fine-tune variant — BASELINE configs[4] names the fine-tune
    # workload specifically ("BERT-base fine-tune via SameDiff TF-import
    # (SQuAD)"): span head over the full encoder, masked batch
    from deeplearning4j_tpu.models.transformer import (
        init_qa_head, make_qa_train_step)

    qa_step = jax.jit(make_qa_train_step(cfg, updater),
                      donate_argnums=(0, 1, 2, 3))
    qa_batch = {
        "tokens": batch["tokens"],
        "segments": jnp.asarray((np.arange(T)[None] >= T // 4)
                                .repeat(B, 0).astype(np.int32)),
        "pad_mask": jnp.asarray(pad),
        "start_positions": jnp.asarray(rs.randint(0, T, B), jnp.int32),
        "end_positions": jnp.asarray(rs.randint(0, T, B), jnp.int32),
    }
    # the MLM-trained encoder + its opt state move into the QA step (their
    # buffers get donated there; `state` is emptied to make that explicit)
    qa_params = init_qa_head(jax.random.key(2), cfg)
    qs = {"p": state.pop("params"), "qa": qa_params,
          "o": state.pop("opt"), "qo": updater.init(qa_params)}

    def run_qa(b):
        qs["p"], qs["qa"], qs["o"], qs["qo"], loss = qa_step(
            qs["p"], qs["qa"], qs["o"], qs["qo"], b, it, rng)
        return loss

    dt_squad = timed(run_qa, qa_batch)
    return {"metric": "bert_mlm_tokens_per_sec",
            "value": round(B * T * p["steps"] / dt, 1), "unit": "tokens/sec/chip",
            "batch": B, "seq": T, "mlm_positions": P,
            "masked_tokens_per_sec": round(B * T * p["steps"] / dt_masked, 1),
            "squad_finetune_tokens_per_sec": round(B * T * p["steps"] / dt_squad, 1),
            "model": "tiny" if p["tiny"] else "bert-base",
            "cost": _trim_table(cost)}


# ------------------------------------------------- multichip: fsdp x tp bert


def bench_fsdp(p):
    """ISSUE 9 multichip section: BERT trained with SHARDED parameters — a
    data=1 × fsdp×tp SpecLayout over every visible device, optimizer state
    sharded with the params, (params, opt) donated through the fused step.
    Reports per-rank param/opt shard bytes next to throughput, and records
    whether the replicated equivalent would fit one chip's HBM (on hardware
    it OOMs for bert-large; the skip reason is part of the result — honest
    models-bigger-than-one-HBM evidence, not a silent omission)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       init_params,
                                                       make_train_step)
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.parallel.partition import Partitioner, SpecLayout
    from deeplearning4j_tpu.parallel.sharding import batch_sharding

    B, T = p["batch"], p["seq"]
    cfg = (TransformerConfig.bert_large(max_len=T, dropout=0.0) if p["large"]
           else TransformerConfig.tiny(max_len=T, dropout=0.0))
    n_dev = len(jax.devices())
    tp = p["tp"] if n_dev % max(p["tp"], 1) == 0 else 1
    layout = SpecLayout(data=1, fsdp=-1, tp=tp)
    partitioner = Partitioner(layout)
    mesh = partitioner.mesh

    updater = Adam(1e-4)
    params = init_params(jax.random.key(0), cfg)
    opt = updater.init(params)
    specs = partitioner.spec_tree(params)
    params = partitioner.place(params, specs)
    opt = partitioner.shard_state_like(opt, specs)
    # publishes tdl_param_bytes_per_rank{kind} + tdl_mesh_layout_info
    report = partitioner.report(params, opt, specs)

    step = jax.jit(make_train_step(cfg, updater), donate_argnums=(0, 1))
    rs = np.random.RandomState(0)
    npos = max(1, int(T * 0.15))
    positions = np.stack([np.sort(rs.choice(T, npos, replace=False))
                          for _ in range(B)])
    bshard = batch_sharding(mesh)  # data axis (size 1 here) — replicated
    batch = {
        "tokens": jax.device_put(
            rs.randint(0, cfg.vocab_size, (B, T)).astype(np.int32), bshard),
        "mlm_positions": jax.device_put(positions.astype(np.int32), bshard),
        "labels": jax.device_put(
            rs.randint(0, cfg.vocab_size, (B, npos)).astype(np.int32), bshard),
        "weights": jax.device_put(np.ones((B, npos), np.float32), bshard),
    }
    rng = jax.random.key(1)
    it = jnp.asarray(0, jnp.int32)

    state = {"p": params, "o": opt}
    del params, opt  # donated into the step from here on

    with jax.sharding.set_mesh(mesh):
        for _ in range(p["warmup"]):
            state["p"], state["o"], loss = step(state["p"], state["o"],
                                                batch, it, rng)
        float(loss)
        t0 = time.perf_counter()
        for _ in range(p["steps"]):
            state["p"], state["o"], loss = step(state["p"], state["o"],
                                                batch, it, rng)
        float(loss)
        dt = time.perf_counter() - t0

    # would the replicated config even fit? params + Adam m/v = 3x param
    # bytes per chip BEFORE activations/grads — compare against the
    # device-reported HBM limit when there is one
    need = 3 * report.params_bytes_total
    stats = getattr(jax.devices()[0], "memory_stats", lambda: None)()
    limit = (stats or {}).get("bytes_limit")
    if limit is None:
        replicated = {"skipped": "no device memory limit reported (cpu "
                                 "smoke) — nothing to OOM against"}
    elif need > 0.5 * limit:
        replicated = {"skipped": f"replicated params+opt need ~{need/2**30:.2f}"
                                 f" GiB/chip vs {limit/2**30:.2f} GiB HBM "
                                 "limit — OOMs where the sharded layout trains"}
    else:
        replicated = {"skipped": f"fits replicated at this scale "
                                 f"(~{need/2**30:.2f} GiB/chip of "
                                 f"{limit/2**30:.2f} GiB) — sharded run is "
                                 "the measurement of record"}
    return {"metric": "bert_fsdp_tokens_per_sec",
            "value": round(B * T * p["steps"] / dt, 1), "unit": "tokens/sec",
            "section": "multichip", "batch": B, "seq": T,
            "model": "bert-large" if p["large"] else "tiny",
            "mesh": {"data": 1, "fsdp": int(mesh.shape[layout.fsdp_axis]),
                     "tp": int(mesh.shape[layout.tp_axis])},
            "param_bytes_total": report.params_bytes_total,
            "param_shard_bytes_per_rank": report.params_bytes_per_rank,
            "opt_state_bytes_per_rank": report.opt_bytes_per_rank,
            "per_device_param_bytes": report.per_device_params_bytes,
            "replicated": replicated}


# ------------------------------------------- multichip: pipeline parallelism


def _pipeline_parallel_measure(p):
    """Measurement core for :func:`bench_pipeline_parallel` — needs >= 2
    devices, so ``bench_pipeline_parallel`` either calls it in-process
    (multi-device hosts) or forks it into a forced-multi-device CPU child.

    Everything here runs the REAL ISSUE 19 code paths, which publish the
    four ``tdl_pipe_*`` families into whichever process executes this:
    the trainer ctor (``tdl_pipe_stages``), ``profile_stages``
    (``tdl_pipe_stage_seconds``), a forced ``maybe_rebalance``
    (``tdl_pipe_rebalances_total`` + the ``pipe_rebalance`` flight event),
    and the forward-schedule bubble fit below (``tdl_pipe_bubble_fraction``).
    """
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       init_params)
    from deeplearning4j_tpu.monitoring.partition import pipe_metrics
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.parallel.pipeline import (PipelineParallelTrainer,
                                                      transformer_pp_loss_fn)
    from deeplearning4j_tpu.parallel.partition import SpecLayout
    from deeplearning4j_tpu.parallel.sharding import batch_sharding

    n_dev = len(jax.devices())
    S = max(s for s in range(2, p["stages"] + 1) if n_dev % s == 0)
    L, T = p["layers"], p["seq"]
    mb, M1, M2 = p["microbatch"], p["m1"], p["m2"]
    cfg = TransformerConfig.tiny(max_len=T, dropout=0.0, n_layers=L)
    layout = SpecLayout(data=n_dev // S, pipe=S)
    trainer = PipelineParallelTrainer(
        init_params(jax.random.key(0), cfg), cfg, Adam(1e-4), layout,
        n_microbatches=M1, schedule="1f1b")
    mesh = trainer.mesh
    pipe_axis = trainer.partitioner.layout.pipe_axis
    rs = np.random.RandomState(0)

    def make_batch(B):
        bshard = batch_sharding(mesh)
        return {"tokens": jax.device_put(
                    rs.randint(0, cfg.vocab_size, (B, T)).astype(np.int32),
                    bshard),
                "labels": jax.device_put(
                    rs.randint(0, cfg.vocab_size, (B, T)).astype(np.int32),
                    bshard)}

    # --- full 1F1B train-step throughput (the headline rate) ---------------
    B1 = mb * M1
    batch = make_batch(B1)
    with jax.sharding.set_mesh(mesh):
        for _ in range(p["warmup"]):
            trainer._fit_batch(batch)
        float(trainer.net.score_)
        t0 = time.perf_counter()
        for _ in range(p["steps"]):
            trainer._fit_batch(batch)
        float(trainer.net.score_)
        step_dt = (time.perf_counter() - t0) / p["steps"]

    # --- measured forward bubble vs the analytic fill-drain bound ----------
    # Fix the microbatch SIZE and vary the microbatch COUNT: a fill-drain
    # schedule costs t(M) ~= c*M + c*(S-1) + const, so the per-microbatch
    # tick cost c falls out of the slope between two M values and whatever
    # fraction of t(M1) is NOT M1*c is idle — fill/drain bubble (plus
    # dispatch constants; repeats amortize those). Analytic: (S-1)/(M+S-1).
    def time_fwd(M, boundaries):
        fn = jax.jit(transformer_pp_loss_fn(
            cfg, M, mesh, pipe_axis=pipe_axis, schedule="1f1b",
            boundaries=boundaries))
        b = make_batch(mb * M)
        with jax.sharding.set_mesh(mesh):
            float(fn(trainer.net.params_, b))  # compile outside the clock
            t0 = time.perf_counter()
            for _ in range(p["fwd_repeats"]):
                out = fn(trainer.net.params_, b)
            jax.block_until_ready(out)
        return (time.perf_counter() - t0) / p["fwd_repeats"]

    t1 = time_fwd(M1, trainer.boundaries)
    t2 = time_fwd(M2, trainer.boundaries)
    c = max(0.0, (t2 - t1) / (M2 - M1))
    bubble = min(1.0, max(0.0, (t1 - M1 * c) / t1)) if t1 > 0 else 0.0
    analytic = (S - 1) / (M1 + S - 1)
    pipe_metrics().bubble.labels("1f1b").set(bubble)

    # --- cost-balanced vs deliberately skewed split ------------------------
    # Transformer blocks are homogeneous, so stage skew is induced the only
    # honest way available: a bad SPLIT (first S-1 stages get one layer
    # each, the last hoards the rest). The pipeline clock runs at the
    # slowest stage, so the balanced split's win should approach
    # max_stage_layers(imbalanced) / max_stage_layers(balanced).
    imbalanced = [(i, i + 1) for i in range(S - 1)] + [(S - 1, L)]
    t_bal = time_fwd(M1, trainer.boundaries)
    t_imb = time_fwd(M1, imbalanced)

    # --- measured stage seconds + a forced skew rebalance ------------------
    stage_seconds = trainer.profile_stages(repeats=max(2, p["fwd_repeats"]))
    predicted = trainer.predicted_stage_costs()
    old_b = list(trainer.boundaries)
    forced = [2.0] + [1.0] * (S - 1)  # stage 0 "measured" 2x slower
    new_b = trainer.maybe_rebalance(forced)
    if new_b is not None:
        with jax.sharding.set_mesh(mesh):
            trainer._fit_batch(batch)  # recompiled step trains on the new split
        float(trainer.net.score_)

    return {"schedule": "1f1b", "stages": S, "layers": L, "seq": T,
            "mesh": {"data": n_dev // S, "pipe": S},
            "tokens_per_sec": round(B1 * T / step_dt, 1),
            "step_ms": round(step_dt * 1e3, 3),
            "microbatches": M1,
            "bubble": {"measured": round(bubble, 4),
                       "analytic_bound": round(analytic, 4),
                       "fwd_ms_m1": round(t1 * 1e3, 3),
                       "fwd_ms_m2": round(t2 * 1e3, 3),
                       "per_microbatch_ms": round(c * 1e3, 3)},
            "balance": {"balanced": [list(x) for x in old_b],
                        "imbalanced": [list(x) for x in imbalanced],
                        "fwd_ms_balanced": round(t_bal * 1e3, 3),
                        "fwd_ms_imbalanced": round(t_imb * 1e3, 3),
                        "speedup": round(t_imb / t_bal, 3) if t_bal > 0
                        else None},
            "stage_seconds": [round(t, 6) for t in stage_seconds],
            "predicted_stage_costs": predicted,
            "rebalance": {"forced_measured": forced, "old": [list(x) for x in old_b],
                          "new": [list(x) for x in new_b] if new_b else None},
            "rebalances_total": 1 if new_b else 0}


def bench_pipeline_parallel(p):
    """ISSUE 19 multichip section: cost-model-balanced pipeline parallelism.

    Reports full 1F1B train-step throughput over a ``data x pipe`` mesh,
    the MEASURED forward-schedule bubble next to the ``(S-1)/(M+S-1)``
    analytic fill-drain bound, the step-time win of the cost-balanced split
    over a deliberately skewed one, and one forced measured-skew rebalance
    (counter + ``pipe_rebalance`` flight event). Single-device hosts (CPU
    smoke without forced devices) fork the measurement into a child with
    ``--xla_force_host_platform_device_count`` and mirror the child-measured
    values into this process's registry so ``--check-telemetry`` still
    proves the four ``tdl_pipe_*`` families alive."""
    import jax

    n_dev = len(jax.devices())
    if any(n_dev % s == 0 for s in range(2, p["stages"] + 1)):
        res = _pipeline_parallel_measure(p)
        res["ran"] = "in-process"
        res["platform"] = jax.devices()[0].platform
    else:
        import subprocess

        forced = int(p.get("force_devices", 4))
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        env["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count={forced}"])
        code = ("import json, bench; print(json.dumps("
                f"bench._pipeline_parallel_measure({dict(p)!r})))")
        proc = subprocess.run(
            [sys.executable, "-c", code], cwd=str(_HERE), env=env,
            capture_output=True, text=True, timeout=900)
        if proc.returncode != 0:
            raise RuntimeError("pipeline_parallel child failed:\n"
                               + proc.stderr[-4000:])
        res = json.loads(proc.stdout.strip().splitlines()[-1])
        # mirror the child-MEASURED values into this process's registry —
        # same numbers, parent-side series, so the families ride the
        # telemetry block the parent snapshots for --check-telemetry
        from deeplearning4j_tpu.monitoring.partition import pipe_metrics
        pm = pipe_metrics()
        pm.stages.set(res["stages"])
        pm.bubble.labels(res["schedule"]).set(res["bubble"]["measured"])
        for i, t in enumerate(res["stage_seconds"]):
            pm.stage_seconds.labels(str(i)).set(t)
        if res["rebalances_total"]:
            pm.rebalances.inc(res["rebalances_total"])
        res["ran"] = f"subprocess ({forced} forced cpu devices)"
        res["platform"] = "cpu"  # whatever backend the parent reports
    return {"metric": "pipeline_parallel_tokens_per_sec",
            "value": res.pop("tokens_per_sec"), "unit": "tokens/sec",
            "section": "multichip", **res}


# ------------------------------------------------------------------- serving


def _latency_ms(latencies):
    """Shared nearest-rank p50/p99 over a SORTED seconds list — the serving
    and serving_pool replays must report identically-computed percentiles."""
    n = len(latencies)
    return {
        "p50_ms": round(latencies[n // 2] * 1e3, 2) if n else None,
        "p99_ms": round(latencies[min(n - 1, int(0.99 * n))] * 1e3, 2)
        if n else None,
    }


def bench_serving(p):
    """ISSUE 5: serving throughput + tail latency through the full stack —
    JsonModelClient → HTTP → bounded admission queue → micro-batching
    executor → ParallelInference bucketed forward. Mean coalesced batch rows
    come from the tdl_inference_batch_size histogram, so the number reported
    here is the same thing /metrics exposes in production."""
    import threading

    from deeplearning4j_tpu.monitoring import get_registry
    from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.serving import JsonModelClient, JsonModelServer

    conf = (NeuralNetConfiguration.Builder().seed(0).updater(Adam(1e-3)).list()
            .layer(DenseLayer(n_in=p["features"], n_out=128, activation="relu"))
            .layer(OutputLayer(n_out=p["classes"], activation="softmax",
                               loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    warm = np.zeros((1, p["features"]), np.float32)
    bs = get_registry().get("tdl_inference_batch_size")
    base = bs.snapshot()["series"][0] if bs and bs.snapshot()["series"] else None
    server = (JsonModelServer.Builder(net).port(0)
              .batch_limit(p["batch_limit"]).queue_size(p["queue"])
              .warmup_input(warm).build().start())
    ready = server.wait_ready(60.0)
    if not ready:
        server.stop()
        return {"metric": "serving_requests_per_sec", "value": 0.0,
                "unit": "req/s", "error": "server never became ready"}
    x = np.random.RandomState(0).randn(1, p["features"]).astype(np.float32).tolist()
    per_client = p["requests"] // p["clients"]
    latencies, errors, lock = [], [0], threading.Lock()

    def worker():
        client = JsonModelClient(port=server.port, retries=3,
                                 backoff_base=0.02, backoff_max=0.25)
        mine = []
        for _ in range(per_client):
            t0 = time.perf_counter()
            try:
                client.predict(x)
                mine.append(time.perf_counter() - t0)
            except RuntimeError:
                with lock:
                    errors[0] += 1
        with lock:
            latencies.extend(mine)

    threads = [threading.Thread(target=worker) for _ in range(p["clients"])]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    server.stop(drain=True)

    latencies.sort()
    n = len(latencies)
    series = get_registry().get("tdl_inference_batch_size").snapshot()["series"]
    snap = series[0] if series else None  # no child if every request failed
    count = (snap["count"] - (base["count"] if base else 0)) if snap else 0
    total = (snap["sum"] - (base["sum"] if base else 0)) if snap else 0.0
    return {
        "metric": "serving_requests_per_sec",
        "value": round(n / elapsed, 1) if elapsed else 0.0,
        "unit": "req/s",
        "clients": p["clients"], "completed": n, "errors": errors[0],
        **_latency_ms(latencies),
        "mean_batch_rows": round(total / count, 2) if count else None,
        "batch_limit": p["batch_limit"],
    }


def bench_serving_slo(p):
    """ISSUE 11: SLO attainment under REPLAYED realistic traffic — a seeded
    diurnal+burst trace through the full client→HTTP→queue→executor stack,
    latency measured client-side, with a history ring + SLO tracker + alert
    engine evaluating live during the replay. The report is what ROADMAP 1's
    autoscaler bench consumes: attainment, error-budget remaining, burn
    rate, and which alert rules fired under the burst."""
    import threading

    from deeplearning4j_tpu.monitoring import (AlertEngine, HistoryRing,
                                               SloTracker, default_objectives,
                                               default_rules, get_registry)
    from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.serving import (Burst, JsonModelServer,
                                            LoadGenerator, TraceSpec)

    conf = (NeuralNetConfiguration.Builder().seed(0).updater(Adam(1e-3)).list()
            .layer(DenseLayer(n_in=p["features"], n_out=128, activation="relu"))
            .layer(OutputLayer(n_out=p["classes"], activation="softmax",
                               loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    warm = np.zeros((1, p["features"]), np.float32)
    server = (JsonModelServer.Builder(net).port(0)
              .batch_limit(p["batch_limit"]).queue_size(p["queue"])
              .warmup_input(warm).build().start())
    if not server.wait_ready(60.0):
        server.stop()
        return {"metric": "slo_attainment", "value": 0.0, "unit": "ratio",
                "error": "server never became ready"}
    dur = p["duration_s"]
    spec = TraceSpec(
        duration_s=dur, base_rate=p["base_rate"], seed=0,
        diurnal_amplitude=0.4,  # one compressed "day" over the replay
        bursts=(Burst(0.5 * dur, 0.15 * dur, p["burst_mult"]),),
        deadline_mix=((0.9, None), (0.1, 2_000.0)))
    threshold_s = p["slo_threshold_ms"] / 1e3
    window_s = max(2.0, dur / 4)
    ring = HistoryRing(registry=get_registry(), interval=0.0)
    tracker = SloTracker(
        default_objectives(latency_threshold_s=threshold_s,
                           target=p["slo_target"], window_s=window_s),
        history_view=ring, registry=get_registry(),
        burn_windows=(("fast", window_s / 2), ("slow", window_s * 2)))
    engine = AlertEngine(
        default_rules(p99_latency_s=threshold_s,
                      latency_window_s=window_s,
                      shed_window_s=window_s),
        registry=get_registry(), history_view=ring)
    fired, stop_eval = set(), threading.Event()

    def evaluate_loop():  # live evaluation at scrape cadence during replay
        while not stop_eval.is_set():
            ring.sample(force=True)
            tracker.evaluate()
            fired.update(a["rule"] for a in engine.evaluate() if a["firing"])
            stop_eval.wait(0.2)

    evaluator = threading.Thread(target=evaluate_loop, daemon=True)
    evaluator.start()
    try:
        report = LoadGenerator(
            spec, server.port, n_clients=p["clients"],
            payload=np.random.RandomState(0)
            .randn(1, p["features"]).astype(np.float32).tolist(),
            slo_threshold_ms=p["slo_threshold_ms"],
            slo_target=p["slo_target"]).run()
    finally:
        stop_eval.set()
        evaluator.join(10.0)
        server.stop(drain=True)
    slo_rows = {r["slo"]: r for r in tracker.evaluate()}
    serving_lat = slo_rows.get("serving_latency", {})
    return {
        "metric": "slo_attainment",
        "value": report["slo"]["attainment"],
        "unit": "ratio",
        "offered": report["offered"],
        "offered_rate_per_s": report["offered_rate_per_s"],
        "outcomes": report["outcomes"],
        "p99_ms": report["latency_ms"]["p99"],
        "slo": report["slo"],
        "tracker": {
            "attainment": serving_lat.get("attainment"),
            "error_budget_remaining":
                serving_lat.get("error_budget_remaining"),
            "burn_rate": serving_lat.get("burn_rate"),
        },
        "alerts_fired_during_replay": sorted(fired),
        "trace": spec.to_dict(),
    }


# -------------------------------------------------------------- serving pool


def _pool_transformer_cfg(p):
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        causal=True, dropout=0.0, attn_impl="xla",
        vocab_size=p["vocab"], max_len=p["max_len"], d_model=p["d_model"],
        n_heads=p["n_heads"], n_layers=p["n_layers"], d_ff=p["d_ff"],
        param_dtype=jnp.float32, compute_dtype=jnp.float32)


def _serving_pool_replica():
    """Replica target (``bench:_serving_pool_replica``) for the serving_pool
    bench: a real KV-cache transformer slot pool behind a generative
    JsonModelServer, shaped by the TDL_BENCH_POOL_CFG env json. Warmup
    restores from the pool's shared compile cache — which is exactly what
    makes the pool's scale-up cheap enough to be alert-driven."""
    import jax
    import numpy as _np

    from deeplearning4j_tpu.models import transformer as tfm
    from deeplearning4j_tpu.serving import JsonModelServer

    p = json.loads(os.environ["TDL_BENCH_POOL_CFG"])
    cfg = _pool_transformer_cfg(p)
    params = tfm.init_params(jax.random.key(0), cfg)
    pool = tfm.PagedDecodeSlotPool(params, cfg, slots=p["slots"])
    return JsonModelServer(
        None, port=0, generative_session=pool,
        default_max_new_tokens=p["max_new"], max_queue=p["queue"],
        warmup_input=_np.asarray([1, 2, 3], _np.int32))


def _replay_generative_executor(ex, spec, prompt_fn, max_new_fn, clients):
    """Open-loop replay of a TraceSpec's arrival schedule straight into a
    generative executor (no HTTP): per-request client-side latency, ok
    count, and wall — the measurement both batching policies share.
    ``max_new_fn(i)`` draws each request's generation budget: HETEROGENEOUS
    lengths are the realistic workload, and exactly what static padded
    batching pays for (a short ride queued behind a long batch member)."""
    import threading

    arrivals = spec.arrivals()
    results = [None] * len(arrivals)
    next_idx = [0]
    lock = threading.Lock()
    t0 = time.perf_counter()

    def worker():
        while True:
            with lock:
                i = next_idx[0]
                if i >= len(arrivals):
                    return
                next_idx[0] = i + 1
            delay = arrivals[i][0] - (time.perf_counter() - t0)
            if delay > 0:
                time.sleep(delay)
            sent = time.perf_counter()
            try:
                fut = ex.submit(prompt_fn(i), max_new_tokens=max_new_fn(i),
                                request_id=f"bench-pool-{i}")
                ok = fut.wait(120.0) and fut.error is None
            except Exception:
                ok = False
            results[i] = {"ok": bool(ok),
                          "latency": time.perf_counter() - sent}

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    lat = sorted(r["latency"] for r in results if r and r["ok"])
    return {
        "offered": len(arrivals),
        "ok": len(lat),
        "elapsed_s": round(elapsed, 3),
        **_latency_ms(lat),
    }


def bench_serving_pool(p):
    """ISSUE 13: the elastic-generative-serving evidence, in two phases.

    Phase 1 — continuous vs STATIC batching at equal load: the same seeded
    diurnal+burst generative trace replayed into a KV-cache slot pool twice,
    once with iteration-level admission (continuous) and once admitting only
    into an empty pool (static padded batching, the DL4J-era policy). The
    acceptance claim is measured, not assumed: p99 strictly lower AND
    tokens/s no worse, with mean decode-slot occupancy reported.

    Phase 2 — the replica pool: N real transformer replicas (subprocesses,
    shared persistent compile cache) behind the least-loaded router replay a
    trace through HTTP, then a manual scale-up measures time-to-ready for a
    NEW replica warming from the cache — the number that prices
    alert-driven autoscaling."""
    import jax

    from deeplearning4j_tpu.models import transformer as tfm
    from deeplearning4j_tpu.serving import (GenerativeInferenceExecutor,
                                            LoadGenerator, ServingPool,
                                            TraceSpec)

    cfg = _pool_transformer_cfg(p)
    params = tfm.init_params(jax.random.key(0), cfg)
    rs = np.random.RandomState(7)
    prompt_lens = (3, 5, 9, 14)
    prompts = [rs.randint(1, p["vocab"], n).tolist() for n in prompt_lens]
    mix = tuple(p.get("max_new_mix") or (p["max_new"],))

    def prompt_fn(i):
        return prompts[i % len(prompts)]

    def max_new_fn(i):
        return mix[i % len(mix)]

    dur = p["duration_s"]
    spec = TraceSpec(duration_s=dur, base_rate=p["base_rate"], seed=0,
                     diurnal_amplitude=0.4,
                     bursts=((0.5 * dur, 0.15 * dur, p["burst_mult"]),))
    phase1 = {}
    for mode, continuous in (("continuous", True), ("static", False)):
        pool = tfm.PagedDecodeSlotPool(params, cfg, slots=p["slots"])
        ex = GenerativeInferenceExecutor(
            pool, continuous=continuous, max_queue=p["queue"],
            default_max_new_tokens=max(mix),
            warmup_prompt=np.asarray([1, 2, 3], np.int32)).start()
        ex.wait_warm(120.0)
        try:
            report = _replay_generative_executor(
                ex, spec, prompt_fn, max_new_fn, p["clients"])
        finally:
            ex.stop(drain=True)
        stats = ex.stats()
        report["tokens_per_s"] = (round(stats["tokens"] / report["elapsed_s"], 1)
                                  if report["elapsed_s"] else 0.0)
        report["mean_slot_occupancy"] = stats["mean_slot_occupancy"]
        report["decode_steps"] = stats["steps"]
        phase1[mode] = report

    cont, stat = phase1["continuous"], phase1["static"]
    p99_ratio = (round(stat["p99_ms"] / cont["p99_ms"], 2)
                 if cont.get("p99_ms") and stat.get("p99_ms") else None)

    # ---- phase 2: the replica pool over HTTP -----------------------------
    import tempfile

    workdir = tempfile.mkdtemp(prefix="tdl_bench_pool_")
    pool = ServingPool(
        "bench:_serving_pool_replica", replicas=p["replicas"],
        min_replicas=1, max_replicas=p["replicas"] + 1, workdir=workdir,
        extra_env={"TDL_BENCH_POOL_CFG": json.dumps(p)})
    pool_report = {"replicas": p["replicas"]}
    try:
        try:
            pool.start()
            if not pool.wait_ready(300.0):
                pool_report["error"] = "pool never became ready"
        except RuntimeError as e:  # e.g. this process holds the chip
            pool_report["error"] = str(e)
        if "error" not in pool_report:
            pdur = p["pool_duration_s"]
            pool_spec = TraceSpec(
                duration_s=pdur, base_rate=p["pool_rate"], seed=1,
                diurnal_amplitude=0.3,
                bursts=((0.5 * pdur, 0.2 * pdur, p["burst_mult"]),))
            replay = LoadGenerator(
                pool_spec, pool.port, n_clients=min(16, p["clients"]),
                payload=prompts[0], slo_threshold_ms=p["slo_threshold_ms"],
                slo_target=p["slo_target"]).run()
            pool_report.update({
                "offered": replay["offered"],
                "outcomes": replay["outcomes"],
                "p99_ms": replay["latency_ms"]["p99"],
                "slo_attainment": replay["slo"]["attainment"],
                "burn_rate_worst_window": replay["slo"]["burn_rate_worst_window"],
            })
            # manual scale-up: time to a READY extra replica, warmed from
            # the shared persistent compile cache (why respawn is cheap)
            t0 = time.perf_counter()
            pool.scale_to(p["replicas"] + 1, reason="bench scale probe")
            deadline = time.monotonic() + 300.0
            while (pool.ready_count < p["replicas"] + 1
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            pool_report["scale_up_ready_s"] = round(
                time.perf_counter() - t0, 2)
            pool_report["scaled_ready"] = pool.ready_count
            pool.scale_to(p["replicas"], reason="bench scale probe done")
            pool_report["replica_states"] = {
                str(k): v for k, v in pool.replica_states().items()}
    finally:
        pool.stop()

    return {
        "metric": "serving_pool_continuous_tokens_per_sec",
        "value": cont["tokens_per_s"],
        "unit": "tokens/s",
        "slots": p["slots"], "max_new_tokens": p["max_new"],
        "continuous": cont,
        "static": stat,
        # the acceptance pair: >1.0 means continuous strictly beat static
        # on p99; tokens/s comparison is read off the two rows directly
        "static_over_continuous_p99": p99_ratio,
        "pool": pool_report,
        "trace": spec.to_dict(),
    }


# ------------------------------------------------------------- paged decoding


def _count_admissions(pool, prompts, max_new):
    """Concurrent sequences a pool holds at once: admit until the first
    refusal (no slot / no blocks), then release everything. Residency is
    priced at admission (a paged pool reserves the FULL span in blocks up
    front), so no decode steps are needed to measure capacity."""
    admitted = []
    for toks in prompts:
        try:
            slot, _ = pool.admit(np.asarray(toks, np.int32), max_new)
        except Exception:
            break
        admitted.append(slot)
    for s in admitted:
        pool.release(s)
    return len(admitted)


def bench_paged_decode(p):
    """ISSUE 17: the paged-KV + speculative-decoding evidence, in two phases.

    Phase 1 — capacity at equal HBM: a dense per-slot pool and a block-paged
    pool get the SAME arena budget (``slots_dense * max_len`` positions;
    the paged pool spends it as ``block_T``-sized blocks plus one trash
    block). Concurrent residency is counted twice: short unique prompts
    (paging wins by not padding every sequence to max_len) and long
    shared-prefix prompts (copy-on-write prefix sharing stacks tenants onto
    one physical prefix). The acceptance claim is >=3x concurrent
    long-context sequences.

    Phase 2 — speculative vs plain decode through the generative executor:
    the same seeded shared-prefix trace (the TraceSpec tenant mix) replayed
    into a paged pool twice, plain and with a draft model proposing
    ``spec_tokens`` per target step. The draft here is the target's first
    ``draft_layers`` layers and the target's tail layers are zeroed into
    identity (pre-LN residual: ``out_w``/``ffn_w2`` = 0 makes a block a
    no-op), so draft and target argmax agree by construction — acceptance
    ~1.0, the best case that bounds the machinery's speedup. Acceptance
    rate is reported alongside; the claim is >=1.5x tokens/s at a p99 no
    worse."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import transformer as tfm
    from deeplearning4j_tpu.serving import (GenerativeInferenceExecutor,
                                            TraceSpec)

    cfg = _pool_transformer_cfg(p)
    params = tfm.init_params(jax.random.key(0), cfg)
    bT, max_new = p["block_T"], p["max_new"]
    n_blocks = 1 + p["slots_dense"] * (p["max_len"] // bT)  # equal HBM

    # ---- phase 1: dense vs paged capacity at equal HBM -------------------
    rng = np.random.default_rng(11)
    n_try = n_blocks + 4
    short = [rng.integers(1, p["vocab"], size=p["short_len"]).tolist()
             for _ in range(n_try)]
    prefixes = [rng.integers(1, p["vocab"], size=p["cap_prefix_len"]).tolist()
                for _ in range(p["prefix_tenants"])]
    shared = [prefixes[i % p["prefix_tenants"]]
              + rng.integers(1, p["vocab"], size=p["cap_suffix_len"]).tolist()
              for i in range(n_try)]

    cap_new = p["cap_max_new"]
    # a dense cache of ``slots_dense`` rows holds that many sequences, whatever
    # their length or shared prefix
    dense_short = dense_long = p["slots_dense"]

    # slots = usable blocks so BLOCKS (HBM), not slot-table rows, bind
    paged_pool = tfm.PagedDecodeSlotPool(
        params, cfg, slots=n_blocks - 1, block_T=bT, n_blocks=n_blocks)
    paged_short = _count_admissions(paged_pool, short, cap_new)
    paged_long = _count_admissions(paged_pool, shared, cap_new)
    capacity = {
        "hbm_positions": p["slots_dense"] * p["max_len"],
        "blocks_usable": n_blocks - 1, "block_T": bT,
        "dense_short": dense_short, "paged_short": paged_short,
        "dense_shared_prefix": dense_long, "paged_shared_prefix": paged_long,
        "gain_short": (round(paged_short / dense_short, 2)
                       if dense_short else None),
        "gain_shared_prefix": (round(paged_long / dense_long, 2)
                               if dense_long else None),
    }

    # ---- phase 2: plain vs speculative through the executor --------------
    # identity-tail target: layers >= draft_layers become exact no-ops, so
    # the first-draft_layers draft predicts the target's argmax exactly
    Ld = p["draft_layers"]
    for blk in params["blocks"][Ld:]:
        blk["out_w"] = jnp.zeros_like(blk["out_w"])
        blk["ffn_w2"] = jnp.zeros_like(blk["ffn_w2"])
    draft_cfg = dataclasses.replace(cfg, n_layers=Ld)
    draft_params = {"embed": params["embed"], "mlm": params["mlm"],
                    "blocks": params["blocks"][:Ld]}

    dur = p["duration_s"]
    spec = TraceSpec(duration_s=dur, base_rate=p["base_rate"], seed=3,
                     diurnal_amplitude=0.3,
                     bursts=((0.5 * dur, 0.2 * dur, 4.0),),
                     prefix_tenants=p["prefix_tenants"],
                     prefix_len=p["prefix_len"], suffix_len=p["suffix_len"],
                     prompt_vocab=p["vocab"])
    prompt_fn = spec.prompt_fn()

    phase2 = {}
    for mode in ("plain", "speculative"):
        kw = {}
        if mode == "speculative":
            kw = dict(draft_params=draft_params, draft_cfg=draft_cfg,
                      spec_tokens=p["spec_tokens"])
        pool = tfm.PagedDecodeSlotPool(
            params, cfg, slots=p["paged_slots"], block_T=bT, **kw)
        ex = GenerativeInferenceExecutor(
            pool, continuous=True, max_queue=p["queue"],
            default_max_new_tokens=max_new,
            warmup_prompt=np.asarray([1, 2, 3], np.int32)).start()
        ex.wait_warm(300.0)
        try:
            report = _replay_generative_executor(
                ex, spec, prompt_fn, lambda i: max_new, p["clients"])
        finally:
            ex.stop(drain=True)
        stats = ex.stats()
        report["tokens_per_s"] = (
            round(stats["tokens"] / report["elapsed_s"], 1)
            if report["elapsed_s"] else 0.0)
        report["decode_steps"] = stats["steps"]
        report["block_occupancy"] = stats.get("block_occupancy")
        report["spec_acceptance"] = stats.get("spec_acceptance")
        report["cow_shared_blocks"] = (
            (stats.get("blocks") or {}).get("cow_shared_blocks"))
        phase2[mode] = report

    plain, spv = phase2["plain"], phase2["speculative"]
    speedup = (round(spv["tokens_per_s"] / plain["tokens_per_s"], 2)
               if plain["tokens_per_s"] else None)
    p99_ratio = (round(plain["p99_ms"] / spv["p99_ms"], 2)
                 if spv.get("p99_ms") and plain.get("p99_ms") else None)

    return {
        "metric": "paged_decode_spec_tokens_per_sec",
        "value": spv["tokens_per_s"],
        "unit": "tokens/s",
        "capacity": capacity,
        "plain": plain,
        "speculative": spv,
        # acceptance pair: speedup >= 1.5 at plain_over_spec_p99 >= 1.0
        "spec_speedup": speedup,
        "plain_over_spec_p99": p99_ratio,
        "spec_tokens": p["spec_tokens"], "draft_layers": Ld,
        "trace": spec.to_dict(),
    }


# --------------------------------------------------------------------- driver


def _baseline_ratio(backend, value, config):
    """Per-backend self-relative trend (ADVICE r1: never cross-compare or
    clobber another backend's baseline; ADVICE r2: only compare runs whose
    measurement config — batch/image size/precision — matches). An
    off-config run, or a missing or unreadable baseline file, reports 1.0.
    Read-only: a bench run never writes into the checkout."""
    per = _HERE / f"BENCH_BASELINE.{backend}.json"
    try:
        d = json.loads(per.read_text())
    except (OSError, ValueError):
        return 1.0
    if d.get("backend") == backend and d.get("config") == config:
        return value / d["value"]
    return 1.0


# ------------------------------------------------------------------- reshard


def _chunked_ckpt_write(lineage_dir, state, fsdp, n_files, iteration=1):
    """Write a COMMITTED lineage generation in TrainingCheckpointer's
    on-disk format AS IF an ``fsdp=<fsdp>`` gang of ``n_files`` processes
    had saved it: each leaf is tiled into fsdp contiguous dim-0 chunks
    (where divisible), the chunks are distributed round-robin over the
    shard files, and the full ISSUE 15 commit record lands — per-rank
    checksummed manifests, self-checksummed meta, COMMIT marker, pointer.
    Lets the bench measure a 4-rank-source restore (which now VERIFIES the
    generation first) on whatever devices this process actually has."""
    # the REAL path-syntax walker + checksum helpers: local copies would
    # silently drift from the on-disk format the restore actually reads
    from deeplearning4j_tpu.serde.checkpoint import (_array_crc, _gen_name,
                                                     _leaf_paths,
                                                     _self_checksummed)

    gen = _gen_name(iteration)
    ckdir = os.path.join(lineage_dir, gen)
    os.makedirs(ckdir, exist_ok=True)
    blobs = [{"__save_id__": np.asarray(iteration, np.int64)}
             for _ in range(n_files)]
    rr = 0
    for path, leaf in _leaf_paths(state):
        if not hasattr(leaf, "dtype"):
            continue
        a = np.asarray(leaf)
        parts = fsdp if a.ndim and a.shape[0] % fsdp == 0 else 1
        step = (a.shape[0] // parts) if a.ndim else 0
        for si in range(parts):
            idx = [[0, n] for n in a.shape]
            chunk = a
            if parts > 1:
                idx[0] = [si * step, (si + 1) * step]
                chunk = a[si * step:(si + 1) * step]
            blob = blobs[rr % n_files]
            rr += 1
            key = f"{path}|{si}"
            blob[key] = chunk
            blob[f"{key}|idx"] = np.asarray(idx, np.int64)
            blob[f"{key}|shape"] = np.asarray(list(a.shape), np.int64)
    layout = {"axes": {"data": 1, "fsdp": fsdp, "tp": 1},
              "axis_names": ["data", "fsdp", "tp"]}
    for proc, blob in enumerate(blobs):
        shard = f"shard_{proc}.npz"
        with open(os.path.join(ckdir, shard), "wb") as f:
            np.savez(f, **blob)
        manifest = _self_checksummed({
            "save_id": iteration, "proc": proc, "shard": shard,
            "process_count": n_files, "layout": layout,
            "entries": {k: _array_crc(v) for k, v in blob.items()},
            "nbytes": int(sum(int(v.nbytes) for v in blob.values()))})
        with open(os.path.join(ckdir, f"manifest_{proc}.json"), "w") as f:
            json.dump(manifest, f)
    meta = {"iteration": iteration, "epoch": 0, "score": None,
            "process_count": n_files, "generation": gen,
            "mesh_layout": layout}
    with open(os.path.join(ckdir, "train_state.json"), "w") as f:
        json.dump(_self_checksummed(meta), f)
    with open(os.path.join(ckdir, "COMMIT"), "w") as f:
        json.dump({"generation": gen, "iteration": iteration,
                   "process_count": n_files}, f)
    with open(os.path.join(lineage_dir, "LATEST"), "w") as f:
        f.write(gen + "\n")
    return ckdir


def _swap_replica():
    """Replica target (``bench:_swap_replica``) for the reshard bench's
    swap-window phase: a small real MLN restored from the TDL_MODEL_CKPT
    checkpoint dir, warmed from the pool's shared persistent compile cache —
    the configuration swap_model prices in production."""
    from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.serde.checkpoint import TrainingCheckpointer
    from deeplearning4j_tpu.serving import JsonModelServer

    p = json.loads(os.environ["TDL_BENCH_SWAP_CFG"])
    conf = (NeuralNetConfiguration.Builder().seed(0).updater(Adam(1e-3)).list()
            .layer(DenseLayer(n_in=p["features"], n_out=p["hidden"],
                              activation="relu"))
            .layer(OutputLayer(n_out=p["classes"], activation="softmax",
                               loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    ckpt = os.environ.get("TDL_MODEL_CKPT")
    if ckpt:
        TrainingCheckpointer(ckpt, async_write=False).restore(net)
    return JsonModelServer(
        net, port=0, max_queue=64,
        warmup_input=np.zeros((1, p["features"]), np.float32))


def bench_reshard(p):
    """ISSUE 14: the cost of elasticity as tracked numbers.

    Phase 1 — the restore matrix: a 4-rank fsdp=4 checkpoint (written in the
    real on-disk format by :func:`_chunked_ckpt_write`) restored onto target
    layouts emulating 4, 2, and 8 ranks (clamped to the devices this process
    has; each row reports what actually ran and whether the saved and target
    layouts matched — a mismatch is a true cross-topology reshard through
    the chunk-intersection path, feeding ``tdl_reshard_*``).

    Phase 2 — the swap window: a 2-replica ServingPool of real MLN replicas
    rolls to a new checkpoint via ``swap_model`` with the persistent compile
    cache warm (the initial spawns populated it), so the reported window is
    restore + deserialization, not XLA compilation."""
    import tempfile

    import jax

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.parallel.mesh import mesh_from_shape
    from deeplearning4j_tpu.parallel.partition import (Partitioner,
                                                       largest_layout)
    from deeplearning4j_tpu.serde.checkpoint import TrainingCheckpointer
    from deeplearning4j_tpu.serving import ServingPool

    def build_net():
        conf = (NeuralNetConfiguration.Builder().seed(0).updater(Adam(1e-3))
                .list()
                .layer(DenseLayer(n_in=p["features"], n_out=p["hidden"],
                                  activation="relu"))
                .layer(OutputLayer(n_out=p["classes"], activation="softmax",
                                   loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init()

    rs = np.random.RandomState(0)
    X = rs.randn(32, p["features"]).astype(np.float32)
    Y = np.eye(p["classes"], dtype=np.float32)[
        rs.randint(0, p["classes"], 32)]
    src = build_net()
    for _ in range(p["steps"]):
        src._fit_batch(DataSet(X, Y))
    state = {"params": src.params_, "updater": src.updater_state,
             "bn": src.bn_state}
    host = {k: jax.tree.map(lambda a: np.asarray(a), v)
            for k, v in state.items()}
    state_bytes = sum(a.nbytes for a in jax.tree.leaves(host))

    n_dev = len(jax.devices())
    out = {"metric": "reshard_restore_ms", "unit": "ms",
           "source": {"ranks": 4, "layout_fsdp": 4,
                      "state_bytes": state_bytes},
           "devices": n_dev, "restore": {}}
    with tempfile.TemporaryDirectory() as d:
        ckdir = os.path.join(d, "ck", "latest")
        _chunked_ckpt_write(ckdir, host, fsdp=4, n_files=4,
                            iteration=int(src.iteration))
        for name, want in (("4_to_4", 4), ("4_to_2", 2), ("4_to_8", 8)):
            tdev = min(want, n_dev)
            layout = largest_layout(tdev)
            part = Partitioner(layout, mesh=mesh_from_shape(
                layout.shape(), devices=jax.devices()[:tdev]))
            fresh = build_net()
            ck = TrainingCheckpointer(os.path.join(d, "ck"),
                                      partitioner=part, reshard=True)
            t0 = time.perf_counter()
            assert ck.restore(fresh)
            wall_ms = (time.perf_counter() - t0) * 1e3
            exact = all(
                np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(jax.tree.leaves(host["params"]),
                                jax.tree.leaves(fresh.params_)))
            out["restore"][name] = {
                "target_devices": tdev,
                "target_layout": part.describe()["axes"],
                "same_layout": part.describe() == {
                    "axes": {"data": 1, "fsdp": 4, "tp": 1},
                    "axis_names": ["data", "fsdp", "tp"]},
                "restore_ms": round(wall_ms, 2),
                "exact": bool(exact),
            }
        out["value"] = out["restore"]["4_to_2"]["restore_ms"]

        # ---- phase 2: the swap window over a live pool ------------------
        v1, v2 = os.path.join(d, "m1"), os.path.join(d, "m2")
        TrainingCheckpointer(v1, async_write=False).save(src)
        src._fit_batch(DataSet(X, Y))  # v2 is a genuinely different model
        TrainingCheckpointer(v2, async_write=False).save(src)
        pool = ServingPool(
            "bench:_swap_replica", replicas=p["replicas"], min_replicas=1,
            max_replicas=p["replicas"] + 1,
            workdir=os.path.join(d, "pool"),
            extra_env={"TDL_BENCH_SWAP_CFG": json.dumps(p),
                       "TDL_MODEL_CKPT": v1})
        swap = {"replicas": p["replicas"]}
        try:
            try:
                pool.start()
                if not pool.wait_ready(300.0):
                    swap["error"] = "pool never became ready"
            except RuntimeError as e:  # e.g. this process holds the chip
                swap["error"] = str(e)
            if "error" not in swap:
                res = pool.swap_model(v2)
                swap.update({
                    # the headline: full rolling swap, compile cache warm
                    "swap_window_s": res["window_s"],
                    "swapped": res["swapped"],
                    "rolled_back": res["rolled_back"],
                    "per_replica_s": round(
                        res["window_s"] / max(1, res["swapped"]), 3),
                })
        finally:
            pool.stop()
        out["swap"] = swap
    return out


# ------------------------------------------------------- checkpoint lineage


def bench_ckpt_lineage(p):
    """ISSUE 15: the price of durability, itemized.

    - ``commit_ms`` vs ``inplace_ms``: a full generational save (shard +
      checksummed manifest + meta + fsync discipline + COMMIT + pointer
      swap) against the pre-lineage strawman (one npz + one rename, no
      verify record, no fsync) — the two-phase-commit overhead in absolute
      terms;
    - ``nofsync_ms``: the same generational save with ``durable=False`` —
      isolates the fsync share of the overhead from the manifest share;
    - ``checksum_mb_per_s``: save-side CRC32 throughput over the real state
      bytes (the per-array manifest entries);
    - ``restore_verify_ms`` vs ``restore_noverify_ms`` and
      ``verify_mb_per_s``: what the pre-restore verification pass costs
      (price it against the PR 13 ``reshard`` block's restore_ms rows —
      same state-size ballpark, different axis of work);
    - ``fallback_restore_ms``: restore latency with the NEWEST generation
      bit-flipped — verify fail + quarantine + walk back to the previous
      commit, the unattended self-heal path.

    Runs the real ``tdl_ckpt_*`` counters hot for ``--check-telemetry``
    (commits, verify failures, quarantines, fallbacks, GC retirements)."""
    import tempfile
    import zlib

    import jax

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.serde.checkpoint import (TrainingCheckpointer,
                                                     verify_checkpoint)

    def build_net(seed=0):
        conf = (NeuralNetConfiguration.Builder().seed(seed)
                .updater(Adam(1e-3)).list()
                .layer(DenseLayer(n_in=p["features"], n_out=p["hidden"],
                                  activation="relu"))
                .layer(OutputLayer(n_out=p["classes"], activation="softmax",
                                   loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init()

    rs = np.random.RandomState(0)
    X = rs.randn(32, p["features"]).astype(np.float32)
    Y = np.eye(p["classes"], dtype=np.float32)[
        rs.randint(0, p["classes"], 32)]
    net = build_net()
    for _ in range(p["steps"]):
        net._fit_batch(DataSet(X, Y))
    state = {"params": net.params_, "updater": net.updater_state,
             "bn": net.bn_state}
    host_leaves = [np.asarray(a) for a in jax.tree.leaves(state)
                   if hasattr(a, "dtype")]
    state_bytes = sum(a.nbytes for a in host_leaves)
    state_mb = state_bytes / (1 << 20)

    out = {"metric": "ckpt_lineage_commit_ms", "unit": "ms",
           "state_bytes": state_bytes}

    with tempfile.TemporaryDirectory() as d:
        # (0) save-side checksum throughput, measured directly on the bytes
        t0 = time.perf_counter()
        for a in host_leaves:
            zlib.crc32(np.ascontiguousarray(a).tobytes())
        crc_s = time.perf_counter() - t0
        out["checksum_mb_per_s"] = round(state_mb / max(crc_s, 1e-9), 1)

        # (1) full durable generational save — the commit wall
        ck = TrainingCheckpointer(os.path.join(d, "durable"),
                                  async_write=False, keep_last=2)
        walls = []
        for i in range(p["saves"]):
            net._fit_batch(DataSet(X, Y))
            t0 = time.perf_counter()
            ck.save(net)
            walls.append((time.perf_counter() - t0) * 1e3)
        out["commit_ms"] = round(min(walls), 2)  # best-of: page cache warm
        out["value"] = out["commit_ms"]
        out["saves"] = p["saves"]

        # (2) same save, fsync off — isolates the durability tax
        ck_nf = TrainingCheckpointer(os.path.join(d, "nofsync"),
                                     async_write=False, durable=False)
        t0 = time.perf_counter()
        ck_nf.save(net)
        out["nofsync_ms"] = round((time.perf_counter() - t0) * 1e3, 2)

        # (3) the old in-place save strawman: one npz + one rename, no
        # manifests, no fsync, no commit record — what PR 15 replaced
        from deeplearning4j_tpu.serde.checkpoint import _leaf_paths

        blob = {}
        for path, leaf in _leaf_paths(state):
            if hasattr(leaf, "dtype"):
                blob[path] = np.asarray(leaf)
        ip_dir = os.path.join(d, "inplace")
        os.makedirs(ip_dir)
        t0 = time.perf_counter()
        tmp = os.path.join(ip_dir, "shard_0.npz.tmp")
        with open(tmp, "wb") as f:
            np.savez(f, **blob)
        os.replace(tmp, os.path.join(ip_dir, "shard_0.npz"))
        with open(os.path.join(ip_dir, "train_state.json"), "w") as f:
            json.dump({"iteration": int(net.iteration)}, f)
        out["inplace_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
        out["commit_overhead_vs_inplace"] = round(
            out["commit_ms"] / max(out["inplace_ms"], 1e-6), 2)

        # (4) restore: verified vs structural-only
        fresh = build_net(seed=9)
        t0 = time.perf_counter()
        assert ck.restore(fresh)
        out["restore_verify_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
        ck_nv = TrainingCheckpointer(os.path.join(d, "durable"),
                                     async_write=False,
                                     verify_on_restore=False)
        fresh = build_net(seed=10)
        t0 = time.perf_counter()
        assert ck_nv.restore(fresh)
        out["restore_noverify_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 2)
        rep = verify_checkpoint(os.path.join(d, "durable"))
        assert rep["ok"], rep
        out["verify_ms"] = round(rep["seconds"] * 1e3, 2)
        out["verify_mb_per_s"] = round(
            (rep["bytes"] / (1 << 20)) / max(rep["seconds"], 1e-9), 1)

        # (5) fallback latency: bit-flip the newest committed shard (the
        # SAME corruption primitive the corrupt_ckpt chaos fault injects),
        # restore walks back one generation (quarantine + older verify)
        from deeplearning4j_tpu.common.faults import _flip_bit_in_shard

        gendir = ck.committed_generation()
        assert _flip_bit_in_shard(gendir) is not None
        fresh = build_net(seed=11)
        t0 = time.perf_counter()
        assert ck.restore(fresh)
        out["fallback_restore_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 2)
        out["fallback_quarantined"] = os.path.basename(gendir)
    return out


# ------------------------------------------------- deployment controller


def bench_deploy(p):
    """ISSUE 18: the price of an unattended promotion decision.

    Walks a real :class:`FleetController` gate chain (no pool — the canary
    leg is priced separately below) over a live lineage:

    - ``promote_ms`` (the headline): integrity deep-verify + offline eval +
      promote bookkeeping for one HEALTHY generation — what the controller
      adds on top of training before a candidate reaches the fleet;
    - ``integrity_reject_ms``: a bit-flipped generation caught at the first
      gate — the cheapest rejection (one verified read, no replica risk);
    - ``eval_reject_ms``: a loss-spiked generation (structurally perfect,
      numbers ruined) caught by the eval gate's threshold + regression band;
    - ``canary_judge_windows_per_s``: throughput of the paired old-vs-
      candidate SLO judgement (window pairing + AlertRule evaluation per
      sub-window) over synthetic replay rows — the gate's analysis cost,
      isolated from the replay's wall time.

    Runs every ``tdl_deploy_*`` and ``tdl_eval_*`` family hot for
    ``--check-telemetry``."""
    import tempfile

    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.deploy import FleetController
    from deeplearning4j_tpu.monitoring import get_registry
    from deeplearning4j_tpu.monitoring.deploy import (canary_rules,
                                                      judge_canary_windows,
                                                      paired_canary_windows)
    from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.serde.checkpoint import TrainingCheckpointer

    conf = (NeuralNetConfiguration.Builder().seed(0).updater(Adam(1e-3))
            .list()
            .layer(DenseLayer(n_in=p["features"], n_out=p["hidden"],
                              activation="relu"))
            .layer(OutputLayer(n_out=p["classes"], activation="softmax",
                               loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rs = np.random.RandomState(0)
    X = rs.randn(32, p["features"]).astype(np.float32)
    Y = np.eye(p["classes"], dtype=np.float32)[
        rs.randint(0, p["classes"], 32)]

    def weight_eval(gendir):
        # spiked generations carry blown-up parameters: a cheap stand-in
        # for a held-out eval with the same verdict structure
        shard = sorted(f for f in os.listdir(gendir)
                       if f.startswith("shard_"))[0]
        with np.load(os.path.join(gendir, shard)) as z:
            mags = [float(np.abs(z[k]).mean()) for k in z.files
                    if k.startswith("params/")and not k.endswith(
                        ("|idx", "|shape"))]
        return {"accuracy": 0.9 if max(mags) < 0.5 else 0.1}

    out = {"metric": "deploy_promote_ms", "unit": "ms"}
    with tempfile.TemporaryDirectory() as d:
        ck = TrainingCheckpointer(os.path.join(d, "ck"), async_write=False,
                                  keep_last=8)
        import jax as _jax

        for _ in range(p["steps"]):
            net._fit_batch(DataSet(X, Y))
        ck.save(net)  # healthy candidate
        ctl = FleetController(os.path.join(d, "ck"),
                              workdir=os.path.join(d, "deploy"),
                              eval_fn=weight_eval,
                              eval_thresholds={"accuracy": 0.8},
                              regression_band=0.1, retries=0,
                              registry=get_registry())
        try:
            t0 = time.perf_counter()
            ctl.run_once()
            out["promote_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
            out["value"] = out["promote_ms"]
            assert ctl.state["promoted"] is not None

            # loss-spiked candidate → eval-gate rejection
            net.params_ = _jax.tree.map(lambda a: a * 40.0, net.params_)
            net._fit_batch(DataSet(X, Y))
            ck.save(net)
            t0 = time.perf_counter()
            rows = ctl.run_once()
            out["eval_reject_ms"] = round(
                (time.perf_counter() - t0) * 1e3, 2)
            assert rows[-1]["rejected_by"]["gate"] == "eval"

            # bit-flipped candidate → integrity-gate rejection
            net._fit_batch(DataSet(X, Y))
            ck.save(net)
            from deeplearning4j_tpu.common.faults import _flip_bit_in_shard

            assert _flip_bit_in_shard(ck.committed_generation()) is not None
            t0 = time.perf_counter()
            rows = ctl.run_once()
            out["integrity_reject_ms"] = round(
                (time.perf_counter() - t0) * 1e3, 2)
            assert rows[-1]["rejected_by"]["gate"] == "integrity"
        finally:
            ctl.close()

    # the to_metrics hook (classification + regression): eval verdicts land
    # on /metrics under the model label
    from deeplearning4j_tpu.eval import Evaluation, RegressionEvaluation

    ev = Evaluation()
    y = np.eye(p["classes"], dtype=np.float32)[
        rs.randint(0, p["classes"], 64)]
    ev.eval(y, y)
    ev.to_metrics(get_registry(), model="bench-clf")
    rev = RegressionEvaluation()
    t = rs.randn(64, 1).astype(np.float32)
    rev.eval(t, t + 0.1 * rs.randn(64, 1).astype(np.float32))
    rev.to_metrics(get_registry(), model="bench-reg")

    # paired canary judgement throughput over synthetic replay rows
    rs = np.random.RandomState(1)
    n = p["canary_requests"]
    dur = 4.0

    def arm_rows(lat_ms):
        return [{"t": float(t), "outcome": "200",
                 "latency_ms": float(max(0.1, rs.normal(lat_ms, 2.0)))}
                for t in np.linspace(0, dur, n, endpoint=False)]

    base, cand = arm_rows(5.0), arm_rows(30.0)
    t0 = time.perf_counter()
    windows = paired_canary_windows(base, cand, duration_s=dur,
                                    window_s=0.25, threshold_ms=10.0,
                                    target=0.99)
    verdict = judge_canary_windows(windows, canary_rules(),
                                   registry=get_registry())
    judge_s = time.perf_counter() - t0
    assert not verdict["ok"]  # the slow arm must trip the paired rules
    out["canary_judge_windows_per_s"] = round(
        verdict["judged"] / max(judge_s, 1e-9), 1)
    out["canary_requests"] = 2 * n
    return out


# ------------------------------------------------------------ trace overhead


def bench_trace_overhead(p):
    """ISSUE 16: what the fleet-timeline instrumentation costs when it is
    ON at default sampling (flight ring + request spans + trace-id
    propagation, span_sample_n=1) vs fully OFF (no TDL_FLIGHT_DIR, no
    recorder). Two steady-state loops — serving req/s through the full
    client→HTTP→executor stack, and the ParallelTrainer step path that
    records step_begin/step_end — measured in alternating rounds so
    machine drift hits both modes equally. Acceptance: ≤2%% at default
    sampling."""
    import tempfile
    import threading

    from deeplearning4j_tpu.monitoring import flight
    from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.serving import JsonModelClient, JsonModelServer

    flight_dir = tempfile.mkdtemp(prefix="tdl_trace_bench_")
    saved_env = os.environ.get(flight.ENV_DIR)

    def set_mode(on: bool) -> None:
        if on:
            os.environ[flight.ENV_DIR] = flight_dir
        else:
            os.environ.pop(flight.ENV_DIR, None)

    def median(vals):
        vals = sorted(vals)
        return vals[len(vals) // 2] if vals else 0.0

    def overhead_pct(off, on, higher_is_better):
        if not off or not on:
            return None
        pct = ((off - on) / off if higher_is_better else (on - off) / off)
        return round(pct * 100.0, 2)

    out = {"metric": "trace_overhead_serving_pct", "unit": "%",
           "rounds": p["rounds"]}
    try:
        # -- serving: req/s with spans+trace propagation on vs off --------
        conf = (NeuralNetConfiguration.Builder().seed(0).updater(Adam(1e-3))
                .list()
                .layer(DenseLayer(n_in=p["features"], n_out=64,
                                  activation="relu"))
                .layer(OutputLayer(n_out=p["classes"], activation="softmax",
                                   loss="mcxent"))
                .build())
        net = MultiLayerNetwork(conf).init()
        warm = np.zeros((1, p["features"]), np.float32)
        set_mode(False)
        server = (JsonModelServer.Builder(net).port(0)
                  .batch_limit(p["batch_limit"]).queue_size(p["queue"])
                  .warmup_input(warm).build().start())
        if not server.wait_ready(60.0):
            server.stop()
            return {**out, "value": None, "error": "server never became ready"}
        x = np.random.RandomState(0).randn(
            1, p["features"]).astype(np.float32).tolist()
        per_client = p["requests_per_round"] // p["clients"]

        def one_round(tag):
            done = [0]
            lock = threading.Lock()

            def worker(ci):
                client = JsonModelClient(port=server.port, retries=2,
                                         backoff_base=0.02, backoff_max=0.25)
                n = 0
                for i in range(per_client):
                    try:  # trace id in BOTH modes: only recording differs
                        client.predict(x, trace_id=f"{tag}-{ci}-{i}")
                        n += 1
                    except RuntimeError:
                        pass
                with lock:
                    done[0] += n

            threads = [threading.Thread(target=worker, args=(ci,))
                       for ci in range(p["clients"])]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            return done[0] / dt if dt else 0.0

        one_round("warm")  # executor warmup outside the measured rounds
        rps_off, rps_on = [], []
        for r in range(p["rounds"]):
            set_mode(False)
            rps_off.append(one_round(f"off{r}"))
            set_mode(True)
            rps_on.append(one_round(f"on{r}"))
        server.stop(drain=True)

        # -- training: ParallelTrainer step path (step_begin/step_end) ----
        from deeplearning4j_tpu.data.dataset import DataSet
        from deeplearning4j_tpu.parallel import ParallelTrainer

        tconf = (NeuralNetConfiguration.Builder().seed(0).updater(Adam(1e-3))
                 .list()
                 .layer(DenseLayer(n_in=p["train_features"],
                                   n_out=p["train_hidden"],
                                   activation="relu"))
                 .layer(OutputLayer(n_out=p["classes"], activation="softmax",
                                    loss="mcxent"))
                 .build())
        tnet = MultiLayerNetwork(tconf).init()
        trainer = ParallelTrainer(tnet)
        rs = np.random.RandomState(0)
        ds = DataSet(
            rs.randn(p["train_batch"], p["train_features"]).astype(np.float32),
            np.eye(p["classes"], dtype=np.float32)[
                rs.randint(0, p["classes"], p["train_batch"])])
        set_mode(False)
        for _ in range(2):
            trainer._fit_batch(ds)  # compile outside the measured rounds

        def train_round():
            t0 = time.perf_counter()
            for _ in range(p["train_steps"]):
                trainer._fit_batch(ds)
            return (time.perf_counter() - t0) / p["train_steps"]

        step_off, step_on = [], []
        for _ in range(p["rounds"]):
            set_mode(False)
            step_off.append(train_round())
            set_mode(True)
            step_on.append(train_round())
    finally:
        if saved_env is None:
            os.environ.pop(flight.ENV_DIR, None)
        else:
            os.environ[flight.ENV_DIR] = saved_env

    r_off, r_on = median(rps_off), median(rps_on)
    s_off, s_on = median(step_off), median(step_on)
    serving_pct = overhead_pct(r_off, r_on, higher_is_better=True)
    train_pct = overhead_pct(s_off, s_on, higher_is_better=False)
    return {**out,
            # headline value = serving overhead (the hot request path; the
            # negative-is-noise convention matches compare_benchmarks)
            "value": serving_pct,
            "serving": {"rps_off": round(r_off, 1), "rps_on": round(r_on, 1),
                        "overhead_pct": serving_pct},
            "train": {"step_ms_off": round(s_off * 1e3, 3),
                      "step_ms_on": round(s_on * 1e3, 3),
                      "overhead_pct": train_pct},
            "span_sample_n": 1, "target_pct": 2.0}


# ------------------------------------------------------------------ hpo fleet


def bench_hpo(p):
    """ISSUE 20: the price of a fault-isolated PBT/ASHA sweep, itemized.

    - ``sweep_s`` vs ``sequential_s`` / ``speedup``: the same N-trial gang
      sweep (real ``GangSupervisor`` gangs over the synth task, one shared
      spool/flight/compile-cache plane) run at ``max_concurrent=K`` against
      one-gang-at-a-time — what the fleet's concurrency is worth at the
      wall clock, per-gang spawn cost included;
    - ``clone_verify_ms`` / ``clone_fallback_ms``: one PBT exploit through
      the REAL fleet path (suffixed-sibling re-save of the winner's newest
      committed generation: deep verify + commit + journal + loser-lineage
      retire), then the same exploit with that generation bit-flipped —
      quarantine the corrupt commit, fall back one generation;
    - ``resume``: SIGKILL the unattended fleet CLI mid-rung, rerun the same
      config, time to a winner — journaled scores are adopted, not re-run;
    - ``etl_cache``: two ``lenet_images`` trials sharing one
      ``DecodedBatchCache`` — the sweep pays the PNG decode once (first
      trial's misses), every later trial memmaps it (hits), read per trial
      from the merged worker spool.

    Phase 0 drives an in-process micro-fleet through every trial-terminal
    decision path (promote / demote / clone / quarantine) on the PROCESS
    registry, so the ``tdl_trial_*`` / ``tdl_fleet_*`` families are hot for
    ``--check-telemetry`` without waiting on real gangs."""
    import shutil
    import signal
    import subprocess
    import tempfile

    from PIL import Image

    from deeplearning4j_tpu.arbiter import (ContinuousParameterSpace,
                                            IntegerParameterSpace,
                                            RandomSearchGenerator)
    from deeplearning4j_tpu.arbiter.fleet import GangTrialRunner, TrialFleet
    from deeplearning4j_tpu.common.faults import _flip_bit_in_shard
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.monitoring import MetricsRegistry, aggregate
    from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import DenseLayer, InputType, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.serde.checkpoint import (TrainingCheckpointer,
                                                     lineage_state)

    spaces = {
        "learning_rate": ContinuousParameterSpace(1e-3, 1e-1, log_scale=True),
        "hidden": IntegerParameterSpace(4, 32),
    }
    spaces_cfg = {
        "learning_rate": {"kind": "continuous", "lo": 1e-3, "hi": 1e-1,
                          "log_scale": True},
        "hidden": {"kind": "integer", "lo": 4, "hi": 32},
    }
    task = {"kind": "synth_classify", "seed": 11}

    def build_small_net(seed=5):
        conf = (NeuralNetConfiguration.Builder().seed(seed)
                .updater(Adam(1e-2)).list()
                .layer(DenseLayer(n_in=4, n_out=8, activation="tanh"))
                .layer(OutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(4)).build())
        return MultiLayerNetwork(conf).init()

    def seed_lineage(directory, steps=2, seed=5):
        # a real committed lineage for PBT to clone from (the in-process
        # phases skip gang training but never fake checkpoint bytes)
        net = build_small_net(seed)
        rs = np.random.RandomState(0)
        x = rs.randn(16, 4).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, 16)]
        ck = TrainingCheckpointer(directory, async_write=False, keep_last=8)
        for _ in range(steps):
            net._fit_batch(DataSet(x, y))
            ck.save(net)

    def micro_runner(slot, target_iter, timeout_s):
        if slot.trial_id == "t05":
            raise RuntimeError("chaos: injected trial crash")
        lr = float(slot.hparams["learning_rate"])
        return 1.0 / (1.0 + abs(np.log10(lr) + 2.0)) + 1e-3 * target_iter

    out = {"metric": "hpo_sweep_speedup", "unit": "x",
           # every gang and the resume CLI run on platform="cpu" by design,
           # whatever backend this process reports in the header
           "platform": "cpu",
           "trials": p["trials"], "rungs": list(p["rungs"]),
           "concurrent": p["concurrent"]}
    tmp = tempfile.mkdtemp(prefix="bench_hpo_")
    try:
        # (0) decision-path micro-fleet on the process registry: one trial
        # crashes past its restart budget (quarantine), the ASHA cut
        # demotes, PBT clones the seeded winner lineage (ok outcome)
        fleet = TrialFleet(
            RandomSearchGenerator(spaces, seed=3), micro_runner,
            workdir=os.path.join(tmp, "micro"), n_trials=6, rungs=(1, 2),
            reduction=2, pbt=True, pbt_quantile=0.34, seed=3,
            trial_max_restarts=1, backoff_base_s=0.01, backoff_max_s=0.02,
            max_concurrent=4, rung_timeout_s=120.0, spaces=spaces)
        for tid, slot in fleet.trials.items():
            if tid != "t05":
                seed_lineage(slot.ckpt_dir)
        try:
            micro_winner = fleet.run()
        finally:
            fleet.close()
        out["micro"] = {
            "winner": micro_winner["trial"],
            "quarantined": sorted(t.trial_id for t in fleet.trials.values()
                                  if t.status == "quarantined"),
            "clones": [r["outcome"] for r in fleet.state["journal"]
                       if r["kind"] == "clone"]}

        # (1) clone + deep-verify latency through the real fleet path, then
        # the same exploit against a bit-flipped newest generation — the
        # quarantine-and-fall-back-one-commit price
        cfleet = TrialFleet(
            RandomSearchGenerator(spaces, seed=9), micro_runner,
            workdir=os.path.join(tmp, "clone"), n_trials=2, rungs=(1,),
            pbt=False, seed=9, spaces=spaces)
        winner, loser = cfleet.trials["t00"], cfleet.trials["t01"]
        seed_lineage(winner.ckpt_dir, steps=2, seed=5)
        seed_lineage(loser.ckpt_dir, steps=1, seed=7)
        t0 = time.perf_counter()
        got = cfleet._clone_into_slot(loser, winner, rung=0)
        out["clone_verify_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
        assert got == "ok", got
        newest = lineage_state(winner.ckpt_dir)["newest_committed"]
        assert _flip_bit_in_shard(
            os.path.join(winner.ckpt_dir, "latest", newest)) is not None
        t0 = time.perf_counter()
        got = cfleet._clone_into_slot(loser, winner, rung=0)
        out["clone_fallback_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
        assert got == "fallback", got
        cfleet.close()

        # (2) the sweep itself: real gangs, concurrent vs one-at-a-time.
        # Same generator seed → identical candidate sets; the sequential
        # baseline keeps its metrics off the process registry so the
        # telemetry block reflects the concurrent sweep
        def gang_sweep(wd, max_concurrent, registry=None):
            gen = RandomSearchGenerator(spaces, seed=p["seed"])
            runner = GangTrialRunner(wd, task, hang_timeout=60.0)
            fl = TrialFleet(
                gen, runner, workdir=wd, n_trials=p["trials"],
                rungs=tuple(p["rungs"]), reduction=2, pbt=True,
                seed=p["seed"], registry=registry, rung_timeout_s=900.0,
                trial_max_restarts=1, backoff_base_s=0.1,
                max_concurrent=max_concurrent)
            t0 = time.perf_counter()
            try:
                win = fl.run()
            finally:
                fl.close()
            return time.perf_counter() - t0, win

        sweep_s, win = gang_sweep(os.path.join(tmp, "sweep"),
                                  p["concurrent"])
        seq_s, _ = gang_sweep(os.path.join(tmp, "seq"), 1,
                              registry=MetricsRegistry())
        out["sweep_s"] = round(sweep_s, 2)
        out["sequential_s"] = round(seq_s, 2)
        out["speedup"] = round(seq_s / max(sweep_s, 1e-9), 2)
        out["value"] = out["speedup"]
        out["winner"] = {"trial": win["trial"],
                         "score": round(win["score"], 4)}

        # (3) SIGKILL the unattended CLI mid-rung, rerun the same config:
        # resume adopts the journaled scores instead of re-running them
        resume_wd = os.path.join(tmp, "resume")
        cfg_path = os.path.join(tmp, "resume_cfg.json")
        with open(cfg_path, "w") as f:
            json.dump({"workdir": resume_wd, "generator": "random",
                       "seed": 13, "n_trials": p["resume_trials"],
                       "rungs": [p["rungs"][0]], "max_concurrent": 1,
                       "pbt": False, "rung_timeout_s": 600.0,
                       "trial_max_restarts": 1, "backoff_base_s": 0.1,
                       "hang_timeout": 60.0, "task": task,
                       "spaces": spaces_cfg}, f)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        cli = [sys.executable, "-m", "deeplearning4j_tpu.arbiter.fleet",
               cfg_path]
        proc = subprocess.Popen(cli, env=env, cwd=str(_HERE),
                                stdout=subprocess.DEVNULL,
                                stderr=subprocess.DEVNULL)
        state_path = os.path.join(resume_wd, "fleet_state.json")
        deadline = time.monotonic() + 300.0
        killed, pre_scores = False, 0
        while time.monotonic() < deadline and proc.poll() is None:
            try:
                rows = json.load(open(state_path))["journal"]
                pre_scores = sum(r["kind"] == "score" for r in rows)
            except (OSError, ValueError, KeyError):
                pre_scores = 0
            if pre_scores >= 1:  # mid-rung: a score is down, no winner yet
                proc.send_signal(signal.SIGKILL)
                proc.wait(timeout=30)
                killed = True
                break
            time.sleep(0.25)
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
        t0 = time.perf_counter()
        res = subprocess.run(cli, env=env, cwd=str(_HERE),
                             capture_output=True, text=True, timeout=600)
        resume_s = time.perf_counter() - t0
        assert res.returncode == 0, res.stdout + res.stderr
        out["resume"] = {"resume_s": round(resume_s, 2),
                         "killed_mid_run": killed,
                         "scores_adopted": pre_scores}

        # (4) shared-ETL-cache evidence: two lenet_images trials, one
        # cache_dir, run one-at-a-time — the second trial's decode traffic
        # should be all hits, read per trial from the merged worker spool
        data_dir = os.path.join(tmp, "imgs")
        rs = np.random.RandomState(0)
        for i in range(int(p["etl_images"])):
            d = os.path.join(data_dir, f"c{i % 4}")
            os.makedirs(d, exist_ok=True)
            Image.fromarray(rs.randint(0, 255, (16, 16), dtype=np.uint8),
                            mode="L").save(os.path.join(d, f"i{i:03d}.png"))
        etl_wd = os.path.join(tmp, "etl")
        etl_task = {"kind": "lenet_images", "data_dir": data_dir,
                    "cache_dir": os.path.join(tmp, "etl_cache"),
                    "height": 12, "width": 12, "channels": 1, "batch": 8,
                    "store_pad": 2, "seed": 5}
        runner = GangTrialRunner(etl_wd, etl_task, hang_timeout=120.0)
        fl = TrialFleet(
            RandomSearchGenerator(
                {"learning_rate": ContinuousParameterSpace(
                    1e-3, 1e-2, log_scale=True)}, seed=5),
            runner, workdir=etl_wd, n_trials=2,
            rungs=(int(p["etl_iters"]),), pbt=False, seed=5,
            max_concurrent=1, rung_timeout_s=900.0, trial_max_restarts=1,
            registry=MetricsRegistry())
        try:
            fl.run()
        finally:
            fl.close()
        by_trial = {}
        for payload in aggregate.read_spools(runner.spool_dir,
                                             registry=MetricsRegistry()):
            trial = str(payload.get("proc") or "").split("-")[0]
            row = by_trial.setdefault(trial, {"hits": 0.0, "misses": 0.0})
            snap = payload.get("snapshot") or {}
            for fam, key in (("tdl_etl_cache_hits_total", "hits"),
                             ("tdl_etl_cache_misses_total", "misses")):
                for s in (snap.get(fam) or {}).get("series", []):
                    row[key] += float(s.get("value", 0))
        out["etl_cache"] = by_trial
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


BENCHES = {"resnet50": bench_resnet50, "lenet": bench_lenet, "lstm": bench_lstm,
           "w2v": bench_w2v, "bert": bench_bert, "serving": bench_serving,
           "serving_slo": bench_serving_slo, "bert_large_fsdp": bench_fsdp,
           "serving_pool": bench_serving_pool, "hpo": bench_hpo,
           "pipeline_parallel": bench_pipeline_parallel,
           "reshard": bench_reshard,
           "ckpt_lineage": bench_ckpt_lineage,
           "deploy": bench_deploy,
           "trace_overhead": bench_trace_overhead,
           "paged_decode": bench_paged_decode}


# -------------------------------------------------------- regression compare


def compare_benchmarks(current: dict, old: dict, threshold: float = 0.10):
    """Per-config throughput regressions of ``current`` vs a prior bench
    JSON (ISSUE 10 satellite: the BENCH trajectory becomes machine-checkable).
    Only rate metrics gate (unit contains "/s"); lower-is-better metrics like
    time-to-accuracy are skipped. Raises ValueError on a cross-backend
    compare — a CPU-smoke run regressing against a TPU baseline is noise,
    not signal."""
    if old.get("backend") != current.get("backend"):
        raise ValueError(
            f"cannot compare backends: current={current.get('backend')!r} "
            f"vs old={old.get('backend')!r}")
    regressions = []
    old_cfgs = old.get("configs") or {}
    for name, cur in (current.get("configs") or {}).items():
        prev = old_cfgs.get(name)
        if not isinstance(cur, dict) or not isinstance(prev, dict):
            continue
        unit = str(cur.get("unit") or "")
        if "/s" not in unit:
            continue
        if str(prev.get("unit") or "") != unit:
            # a config whose unit changed between runs is incomparable —
            # ratioing images/sec against batches/sec fabricates a
            # regression (or hides one behind a unit inflation)
            continue
        cv, pv = cur.get("value"), prev.get("value")
        # a prior value of None/0 gives no baseline; a CURRENT value of 0
        # against a real baseline is the worst regression there is — it must
        # gate, not fall through a falsy check
        if cv is None or pv is None or pv <= 0:
            continue
        ratio = cv / pv
        if ratio < 1.0 - threshold:
            regressions.append({"config": name, "old": pv, "new": cv,
                                "ratio": round(ratio, 3), "unit": unit})
    return regressions


def config_errors(results):
    """Every ``"error"`` field anywhere under the per-config results, as
    ``(dotted path, message)`` — phases nest their reports (``pool.error``,
    ``swap.error``), so the search is recursive."""
    found = []

    def walk(node, path):
        if isinstance(node, dict):
            for k, v in node.items():
                if k == "error" and v:
                    found.append((path, str(v)))
                else:
                    walk(v, f"{path}.{k}")
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                walk(v, f"{path}[{i}]")

    for name, res in results.items():
        walk(res, name)
    return found


# -------------------------------------------------------- telemetry checking


def documented_bench_families(doc_path=None):
    """Metric families docs/OBSERVABILITY.md marks as exercised by a full
    bench run (a ``bench`` cell containing ``yes``). The doc's catalog table
    is the single source of truth, so a family added to the code without a
    catalog row — or documented but silently dead (the PR 1
    ``last_batch_size`` bug class) — fails ``--check-telemetry``."""
    import re

    path = pathlib.Path(doc_path) if doc_path else (
        _HERE / "docs" / "OBSERVABILITY.md")
    families = []
    for line in path.read_text().splitlines():
        m = re.match(r"\|\s*`(tdl_[a-z0-9_]+)`\s*\|", line)
        if not m:
            continue
        cells = [c.strip() for c in line.strip().strip("|").split("|")]
        if cells and cells[-1].lower().startswith("yes"):
            families.append(m.group(1))
    if not families:
        raise RuntimeError(f"no bench-marked metric families parsed from {path}")
    return families


def check_telemetry(out, families):
    """Families documented as bench-exercised but absent (or observation-free)
    in the telemetry block. Histograms with zero observations and counters
    never incremented count as missing — a dead metric that still registers
    itself is exactly the failure mode this catches."""
    metrics = (out.get("telemetry") or {}).get("metrics") or {}
    missing = []
    for fam in families:
        snap = metrics.get(fam)
        series = (snap or {}).get("series") or []
        if snap and snap.get("type") == "histogram":
            # a registered-but-never-observed histogram is dead
            alive = any(s.get("count", 0) > 0 for s in series)
        else:
            # counters/gauges create a series on first touch; a series whose
            # value drained back to 0 (queue depth) is still alive
            alive = bool(series)
        if not alive:
            missing.append(fam)
    return missing


def main():
    import jax

    from deeplearning4j_tpu.monitoring import (DeviceMemoryWatchdog,
                                               RecompileWatchdog, get_registry)

    # telemetry riding along with every bench run: XLA compile count/seconds
    # (recompile storms show up as a compile counter out of proportion to the
    # config count) + device-memory high-water per window
    recompile_wd = RecompileWatchdog().install()
    memory_wd = DeviceMemoryWatchdog()

    backend = jax.default_backend()
    params = _scale(backend == "tpu")
    argv = [a for a in sys.argv[1:] if a != "--check-telemetry"]
    check = "--check-telemetry" in sys.argv[1:]
    compare_path, compare_old = None, None
    if "--compare" in argv:
        i = argv.index("--compare")
        if i + 1 >= len(argv):
            sys.exit("--compare needs a prior bench JSON path")
        compare_path = argv[i + 1]
        del argv[i:i + 2]
        # load + validate NOW: a typo'd path must fail in under a second,
        # not after the whole bench run completes
        try:
            with open(compare_path) as f:
                compare_old = json.load(f)
        except (OSError, ValueError) as e:
            sys.exit(f"--compare cannot read {compare_path}: {e}")
        if not isinstance(compare_old.get("configs"), dict):
            sys.exit(f"--compare: {compare_path} is not a bench JSON "
                     "(no 'configs' object)")
        if compare_old.get("backend") != backend:
            # fail before the run, not after minutes of benching
            sys.exit(f"--compare refused: current backend {backend!r} vs "
                     f"{compare_old.get('backend')!r} in {compare_path}")
    args = argv
    only = args[0] if args else None
    if only and only not in BENCHES:
        sys.exit(f"unknown benchmark {only!r}; choose from: {', '.join(BENCHES)}")
    names = [only] if only else list(BENCHES)
    if check and only:
        sys.exit("--check-telemetry needs the full run (every documented "
                 "family must get a chance to appear); drop the config name")

    results = {}
    for name in names:
        # same-window calibration BEFORE each config (VERDICT r3 weak #3):
        # lets a later run tell code deltas from machine-window deltas.
        # TPU-only: ~0.8 TFLOP of matmuls would dominate the CPU smoke path
        cal = calibration_probe() if backend == "tpu" else None
        results[name] = BENCHES[name](params[name])
        if cal is not None:
            results[name]["calibration"] = cal
        memory_wd.sample()  # high-water gauge tracks the max across configs

    from deeplearning4j_tpu.common.precision import compute_dtype

    # ISSUE 10: one SLO-alert pass over everything the run just emitted —
    # evaluated BEFORE the registry snapshot so tdl_alert_firing rides the
    # telemetry block (a bench run with a firing alert is visibly abnormal).
    # after_warmup rules have no warmup mark in a one-shot bench run and
    # stay pending — reported as such, never silently "clean"
    from deeplearning4j_tpu.monitoring import AlertEngine

    alert_rows = AlertEngine().evaluate()

    effective_precision = compute_dtype().__name__  # resolves 'auto' per backend
    head = results.get("resnet50") or results[names[0]]
    head_cfg = {"batch": head.get("batch"), "image_size": head.get("image_size"),
                "matmul_precision": effective_precision}
    dev0 = jax.devices()[0]
    out = {
        "metric": head["metric"],
        "value": head["value"],
        "unit": head["unit"],
        "vs_baseline": round(_baseline_ratio(backend, head["value"], head_cfg), 3)
        if head["metric"] == "resnet50_train_images_per_sec" else 1.0,
        "backend": backend,
        # the device as jax reports it; a config that ran its children
        # elsewhere says so in its own "platform" field
        "platform": dev0.platform,
        "device_kind": dev0.device_kind,
        "device_count": len(jax.devices()),
        "matmul_precision": effective_precision,
        "configs": results,
        # full registry snapshot: compile counters, memory watermarks, and
        # whatever metrics the exercised code paths emitted — BENCH files
        # carry telemetry from here on
        "telemetry": {"compiles": recompile_wd.stats(),
                      "metrics": get_registry().snapshot()},
        "alerts": {"firing": [a["rule"] for a in alert_rows if a["firing"]],
                   "pending_warmup": [a["rule"] for a in alert_rows
                                      if a["state"] == "pending_warmup"],
                   "evaluated": len(alert_rows)},
    }
    # step-time attribution headline (ISSUE 7): the ResNet-50 pipeline's
    # phase-percentage table, mirrored into the telemetry block
    pipeline = (results.get("resnet50") or {}).get("pipeline") or {}
    if "phases" in pipeline:
        out["telemetry"]["step_phases"] = pipeline["phases"]
    recompile_wd.close()
    print(json.dumps(out))
    errors = config_errors(results)
    if errors:
        # a config that reports "error" did not measure what its name says
        sys.exit("bench config(s) reported an error: " + "; ".join(
            f"{path}: {msg}" for path, msg in errors))
    if check:
        missing = check_telemetry(out, documented_bench_families())
        if missing:
            sys.exit("documented metric families missing/observation-free in "
                     f"the telemetry block (silently dead?): {missing}")
        print("check-telemetry: all documented bench families present",
              file=sys.stderr)
    if compare_path:
        # perf-regression gate (ISSUE 10 satellite): non-zero exit on >10%
        # per-config throughput drops vs the prior BENCH_r*.json
        try:
            regs = compare_benchmarks(out, compare_old)
        except ValueError as e:
            sys.exit(f"--compare refused: {e}")
        if regs:
            for r in regs:
                print(f"REGRESSION {r['config']}: {r['old']} -> {r['new']} "
                      f"{r['unit']} ({r['ratio']:.3f}x)", file=sys.stderr)
            sys.exit(f"{len(regs)} config(s) regressed >10% vs {compare_path}")
        print(f"compare: no >10% throughput regressions vs {compare_path}",
              file=sys.stderr)


if __name__ == "__main__":
    main()
