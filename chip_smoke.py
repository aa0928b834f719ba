#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives tdl's main paths once, in ONE process that owns the chip, through the
entry points a user calls, at BERT-base / ResNet-50 width with random
weights from a seed:

- ``train``   — ``make_train_step`` + Adam, jitted and donated like bench.py,
                MLM positions + a key-padding mask (the masked Pallas flash
                path); loss must fall, and the lowered step must contain a
                Mosaic ``tpu_custom_call``.
- ``serve``   — ``JsonModelServer`` in generative mode over a
                ``PagedDecodeSlotPool``; concurrent ``JsonModelClient``
                requests must answer 200 with the tokens offline
                ``generate()`` produces.
- ``nn``      — the DL4J front door: ``ResNet50().init()`` +
                ``ComputationGraph.fit(iterator)``.
- ``kernels`` — compiled ``flash_attention`` fwd+bwd against an fp32
                ``mha_reference``, incl. the pad shim, causal Tq != Tk, and
                the (512, 1024)-block long-T entry.
- ``mesh``    — with >= 4 devices only: the train step again under
                ``Partitioner(SpecLayout(data=2, fsdp=1, tp=2))``.

    python3 chip_smoke.py              # refuses anything but a TPU
    python3 chip_smoke.py --rehearsal  # tiny sizes, any backend; for CPU

Every phase prints one JSON line, then a ``summary`` line (phases, compile
seconds, cache hits/misses, ``"claim": null``); any failing phase raises
(non-zero exit, no result line). The LAST stdout line is the result the driver
parses, with exactly these keys and nothing else:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
Times printed here are information for PERF.md, not a benchmark: each is one
sample, compile included where it says so.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time

import numpy as np

# one sample point per phase; the rehearsal keeps every code path and shrinks
# every dimension so the same script runs on the CPU in about a minute
FULL = dict(
    model=dict(),  # TransformerConfig.bert_base defaults: 768 x 12 heads x 12 layers
    train=dict(batch=16, seq=512, steps=6),
    serve=dict(slots=8, block_T=32, max_new=32,
               prompt_lens=(5, 20, 40, 60, 100, 129, 200, 250)),
    nn=dict(classes=1000, hw=224, batch=128, batches=3),
    kernels=dict(
        parity=[  # (name, B, H, Tq, Tk, causal, ragged key-padding mask)
            ("unmasked_T512", 2, 12, 512, 512, False, False),
            ("keypad_T500", 2, 12, 500, 500, False, True),
            ("causal_Tq256_Tk512", 2, 12, 256, 512, True, False),
            ("unmasked_T2048", 1, 12, 2048, 2048, False, False),
        ],
        long=(1, 12, 8192, 64)),
)
REHEARSAL = dict(
    model=dict(vocab_size=512, d_model=64, n_heads=2, n_layers=2, d_ff=128),
    train=dict(batch=4, seq=128, steps=5),
    serve=dict(slots=4, block_T=16, max_new=4,
               prompt_lens=(3, 9, 17, 30, 20, 129, 130, 140)),
    nn=dict(classes=10, hw=32, batch=4, batches=3),
    kernels=dict(
        parity=[
            ("unmasked_T256", 1, 2, 256, 256, False, False),
            ("keypad_T200", 1, 2, 200, 200, False, True),
            ("causal_Tq128_Tk256", 1, 2, 128, 256, True, False),
        ],
        long=(1, 1, 4096, 64)),
)


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def aot(jitted, *args):
    """(lowered, compiled, compile seconds) of one jitted function."""
    lowered = jitted.lower(*args)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    return lowered, compiled, time.perf_counter() - t0


# --------------------------------------------------------------------- train


def mlm_batch(cfg, batch: int, seq: int):
    """The bench.py BERT batch plus a ragged key-padding mask: host arrays."""
    rs = np.random.RandomState(0)
    npos = max(1, int(seq * 0.15))
    lengths = rs.randint(int(seq * 0.6), seq + 1, batch)
    pad = (np.arange(seq)[None, :] < lengths[:, None]).astype(np.float32)
    positions = np.stack([np.sort(rs.choice(int(n), npos, replace=False))
                          for n in lengths])
    return {
        "tokens": rs.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32),
        "mlm_positions": positions.astype(np.int32),
        "labels": rs.randint(0, cfg.vocab_size, (batch, npos)).astype(np.int32),
        "weights": np.ones((batch, npos), np.float32),
        "pad_mask": pad,
    }


def run_train_steps(cfg, params, opt, batch, steps: int, on_tpu: bool,
                    **jit_kw):
    """Compile the donated train step ahead of time, assert the Mosaic call
    is in it, take ``steps`` steps on the fixed batch. Returns the phase's
    numbers; raises unless the loss is finite and falls."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import make_train_step
    from deeplearning4j_tpu.nn.updaters import Adam

    step = jax.jit(make_train_step(cfg, Adam(1e-4)), donate_argnums=(0, 1),
                   **jit_kw)
    rng = jax.random.key(1)
    lowered, compiled, compile_s = aot(
        step, params, opt, batch, jnp.asarray(0, jnp.int32), rng)
    mosaic_calls = lowered.as_text().count("tpu_custom_call")
    if on_tpu and not mosaic_calls:
        raise AssertionError(
            "no Mosaic tpu_custom_call in the lowered train step: the flash "
            "kernel did not compile into it (interpret mode or the dense "
            "reference stood in)")
    losses, walls = [], []
    for i in range(steps):
        t0 = time.perf_counter()
        params, opt, loss = compiled(params, opt, batch,
                                     jnp.asarray(i, jnp.int32), rng)
        loss.block_until_ready()
        walls.append(time.perf_counter() - t0)
        losses.append(float(loss))
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"non-finite train loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"train loss did not fall: {losses}")
    return dict(compile_s=round(compile_s, 2),
                step_s=round(float(np.median(walls[1:])), 4),
                first_step_s=round(walls[0], 4),
                losses=[round(x, 4) for x in losses],
                mosaic_calls=mosaic_calls), params


def train_state(sizes, on_tpu: bool):
    """(cfg, params, Adam state, host batch): same seed and batch for the
    one-chip and the sharded phase, so their first losses are comparable."""
    import jax

    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       init_params)
    from deeplearning4j_tpu.nn.updaters import Adam

    p = sizes["train"]
    cfg = TransformerConfig.bert_base(
        max_len=p["seq"], dropout=0.0,
        # off the chip "auto" would pick the dense path; the rehearsal names
        # the kernel so the masked flash route still runs (interpreted)
        attn_impl="auto" if on_tpu else "flash", **sizes["model"])
    params = init_params(jax.random.key(0), cfg)
    return (cfg, params, Adam(1e-4).init(params),
            mlm_batch(cfg, p["batch"], p["seq"]))


def phase_train(sizes, on_tpu: bool):
    import jax

    p = sizes["train"]
    cfg, params, opt, batch = train_state(sizes, on_tpu)
    out, _ = run_train_steps(cfg, params, opt, jax.device_put(batch),
                             p["steps"], on_tpu)
    out.update(batch=p["batch"], seq=p["seq"], n_layers=cfg.n_layers,
               d_model=cfg.d_model, tokens_per_step=p["batch"] * p["seq"])
    return out


# ---------------------------------------------------------------------- mesh


def phase_mesh(sizes, on_tpu: bool, one_chip_first_loss: float):
    """The train phase again, sharded data=2 x tp=2 over four devices."""
    import jax

    from deeplearning4j_tpu.parallel.partition import Partitioner, SpecLayout
    from deeplearning4j_tpu.parallel.sharding import batch_sharding

    p = sizes["train"]
    cfg, params, opt, batch = train_state(sizes, on_tpu)
    layout = SpecLayout(data=2, fsdp=1, tp=2)
    devices = jax.devices()[:4]
    partitioner = Partitioner(layout, mesh=layout.build_mesh(devices))
    mesh = partitioner.mesh

    def in_use():  # None per device where the backend reports nothing (CPU)
        return [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]

    before = in_use()  # device 0 may still hold the earlier phases' leftovers

    specs = partitioner.spec_tree(params)
    params = partitioner.place(params, specs)
    opt = partitioner.shard_state_like(opt, specs)
    batch = jax.device_put(batch, batch_sharding(mesh))
    # new state keeps the placement of the old: left to itself GSPMD hands
    # 1-D leaves back under another spec, and the donated step would then
    # not accept its own output
    keep = tuple(jax.tree.map(lambda x: x.sharding, t) for t in (params, opt))
    with jax.sharding.set_mesh(mesh):
        out, params = run_train_steps(cfg, params, opt, batch, p["steps"],
                                      on_tpu, out_shardings=(*keep, None))

    # (a) nothing piled on device 0: a tp-sharded leaf lives on 4 devices
    leaf = params["blocks"][0]["qkv_w"]
    shard_devices = {s.device for s in leaf.addressable_shards}
    if len(shard_devices) != 4:
        raise AssertionError(f"qkv_w shards on {len(shard_devices)} devices")
    if leaf.addressable_shards[0].data.shape[1] * 2 != leaf.shape[1]:
        raise AssertionError("qkv_w is not split over tp")
    after = in_use()
    held = None
    if on_tpu:
        if None in before + after:
            raise AssertionError(f"no memory_stats on {devices}")
        held = [a - b for a, b in zip(after, before)]  # this phase's state
        if min(held) <= 0 or max(held) > 2 * min(held):
            raise AssertionError(
                f"sharded state is not spread evenly: bytes_in_use grew by "
                f"{held} on {devices}")
    # (b) same seed, same batch: the first loss is the one-chip loss
    rel = abs(out["losses"][0] - one_chip_first_loss) / one_chip_first_loss
    if rel > 2e-2:
        raise AssertionError(
            f"sharded first loss {out['losses'][0]} vs one-chip "
            f"{one_chip_first_loss}: rel {rel:.3g} > bf16 tolerance 2e-2")
    out.update(layout=partitioner.describe()["axes"],
               shard_devices=len(shard_devices), state_bytes_per_device=held,
               first_loss_rel_diff=round(rel, 6))
    return out


# --------------------------------------------------------------------- serve


def phase_serve(sizes, on_tpu: bool):
    import urllib.request

    import jax

    from deeplearning4j_tpu.models import transformer as tfm
    from deeplearning4j_tpu.models.paged_decode import PagedDecodeSlotPool
    from deeplearning4j_tpu.serving.json_server import (JsonModelClient,
                                                        JsonModelServer)

    p = sizes["serve"]
    cfg = tfm.TransformerConfig.bert_base(
        causal=True, dropout=0.0, attn_impl="auto" if on_tpu else "flash",
        **sizes["model"])
    params = tfm.init_params(jax.random.key(0), cfg)
    rs = np.random.RandomState(3)
    prompts = [rs.randint(1, cfg.vocab_size, n).astype(np.int32)
               for n in p["prompt_lens"]]

    def new_pool():
        return PagedDecodeSlotPool(params, cfg, slots=p["slots"],
                                   block_T=p["block_T"])

    pool = new_pool()
    server = (JsonModelServer.Builder(None).generative(pool)
              .max_new_tokens(p["max_new"]).warmup_input(prompts[0])
              .deadline_ms(900_000).build())
    t0 = time.perf_counter()
    server.start()
    try:
        if not server.wait_ready(900):
            raise AssertionError("server never became ready")
        ready_s = time.perf_counter() - t0

        # readiness proves nothing (a failed warmup still reports ready):
        # assert on the ANSWERS of concurrent clients
        answers: dict = {}

        def ask(i):
            client = JsonModelClient(port=server.port, timeout=900,
                                     retries=0, deadline_ms=900_000)
            try:
                answers[i] = client.predict(prompts[i])
            except Exception as e:  # re-raised below, in the main thread
                answers[i] = e

        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(len(prompts))]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(900)
        requests_s = time.perf_counter() - t0
        for i in range(len(prompts)):
            if isinstance(answers.get(i), Exception):
                raise AssertionError(
                    f"request {i} (prompt of {len(prompts[i])} tokens) "
                    f"failed") from answers[i]
            if i not in answers:
                raise AssertionError(f"request {i} never returned")
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/stats", timeout=30) as resp:
            stats = json.loads(resp.read())["stats"]
    finally:
        server.stop()
    if not stats["steps"] > 0:
        raise AssertionError(f"/stats shows no decode steps: {stats}")
    if pool.decode_traces != 1:
        raise AssertionError(f"decode traced {pool.decode_traces}x, not once")

    # same pool geometry => same executables: tokens must be identical
    t0 = time.perf_counter()
    expected = tfm.generate(params, prompts, p["max_new"], cfg,
                            pool=new_pool())
    offline_s = time.perf_counter() - t0
    for i, want in enumerate(expected):
        got = [int(t) for t in np.asarray(answers[i]).reshape(-1)]
        if got != [int(t) for t in want]:
            raise AssertionError(
                f"request {i}: served tokens {got} != offline generate() "
                f"{want}")
    return dict(compile_s=round(ready_s, 2),  # start() -> ready: warmup compiles
                requests_s=round(requests_s, 2),  # incl. new prefill buckets
                offline_generate_s=round(offline_s, 2),
                requests=len(prompts), max_new=p["max_new"],
                decode_steps=stats["steps"], tokens=stats["tokens"],
                mean_slot_occupancy=stats["mean_slot_occupancy"],
                prefill_traces=pool.prefill_traces,
                decode_traces=pool.decode_traces)


# ------------------------------------------------------------------------ nn


def phase_nn(sizes, on_tpu: bool):
    from deeplearning4j_tpu.data import ArrayDataSetIterator
    from deeplearning4j_tpu.models import ResNet50

    p = sizes["nn"]
    n = p["batch"] * p["batches"]
    rs = np.random.RandomState(0)
    x = rs.rand(n, 3, p["hw"], p["hw"]).astype(np.float32)
    y = np.eye(p["classes"], dtype=np.float32)[rs.randint(0, p["classes"], n)]

    net = ResNet50(num_classes=p["classes"],
                   input_shape=(3, p["hw"], p["hw"])).init()
    t0 = time.perf_counter()
    net.fit(ArrayDataSetIterator(x[:p["batch"]], y[:p["batch"]], p["batch"]))
    first = float(net.score())  # reading the score waits for the device
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    net.fit(ArrayDataSetIterator(x, y, p["batch"]))
    last = float(net.score())
    step_s = (time.perf_counter() - t0) / p["batches"]
    if not (np.isfinite(first) and np.isfinite(last)):
        raise AssertionError(f"non-finite ResNet-50 score: {first}, {last}")
    if net.iteration != 1 + p["batches"]:
        raise AssertionError(f"fit took {net.iteration} steps")
    return dict(compile_s=round(first_s - step_s, 2),  # first fit minus a step
                step_s=round(step_s, 4),  # host batch upload included
                batch=p["batch"], image_size=p["hw"], classes=p["classes"],
                first_score=round(first, 4), last_score=round(last, 4))


# ------------------------------------------------------------------- kernels


def phase_kernels(sizes, on_tpu: bool):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.kernels import flash_attention, mha_reference

    interpret = not on_tpu  # on the chip: the compiled kernel, explicitly
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    tol = 2e-2 if on_tpu else 2e-4  # max error / max |reference|
    D = 64
    compile_s, cases = 0.0, {}

    def qkv(B, H, Tq, Tk, seed):
        ks = jax.random.split(jax.random.key(seed), 4)
        return (jax.random.normal(ks[0], (B, H, Tq, D), dtype),
                jax.random.normal(ks[1], (B, H, Tk, D), dtype),
                jax.random.normal(ks[2], (B, H, Tk, D), dtype),
                jax.random.normal(ks[3], (B, H, Tq, D), dtype))

    def fwd_bwd(attn):
        def run(q, k, v, ct, mask):
            out, vjp = jax.vjp(lambda q, k, v: attn(q, k, v, mask), q, k, v)
            return (out,) + vjp(ct.astype(out.dtype))
        return jax.jit(run)

    for seed, (name, B, H, Tq, Tk, causal, ragged) in enumerate(
            sizes["kernels"]["parity"]):
        q, k, v, ct = qkv(B, H, Tq, Tk, seed)
        mask = None
        if ragged:
            lengths = np.linspace(Tk // 2, Tk, B).astype(np.int32)
            mask = jnp.asarray(np.arange(Tk)[None, :] < lengths[:, None],
                               jnp.float32)
        flash = fwd_bwd(lambda q, k, v, m, c=causal: flash_attention(
            q, k, v, m, causal=c, interpret=interpret))
        lowered, compiled, cs = aot(flash, q, k, v, ct, mask)
        compile_s += cs
        if on_tpu and "tpu_custom_call" not in lowered.as_text():
            raise AssertionError(f"{name}: no Mosaic call was lowered")
        t0 = time.perf_counter()
        got = jax.block_until_ready(compiled(q, k, v, ct, mask))
        wall = time.perf_counter() - t0
        # the oracle: dense attention on fp32 copies at full matmul precision
        ref = fwd_bwd(lambda q, k, v, m, c=causal: mha_reference(
            q, k, v, m, causal=c))
        with jax.default_matmul_precision("highest"):
            want = ref(*(t.astype(jnp.float32) for t in (q, k, v, ct)), mask)
        errs = []
        for g, w in zip(got, want):
            g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
            if not np.all(np.isfinite(g)):
                raise AssertionError(f"{name}: non-finite flash output")
            errs.append(float(np.max(np.abs(g - w)) / np.max(np.abs(w))))
        if max(errs) > tol:
            raise AssertionError(
                f"{name}: flash vs reference (out, dq, dk, dv) max error / "
                f"max |ref| = {errs} > {tol}")
        cases[name] = dict(max_rel_err=round(max(errs), 5),
                           fwd_bwd_s=round(wall, 4))

    # long T: the static table answers 1024 x 1024 blocks; compiled + finite
    B, H, T, D = sizes["kernels"]["long"]
    q, k, v, ct = qkv(B, H, T, T, 99)
    flash = fwd_bwd(lambda q, k, v, m: flash_attention(
        q, k, v, m, interpret=interpret))
    lowered, compiled, cs = aot(flash, q, k, v, ct, None)
    compile_s += cs
    if on_tpu and "tpu_custom_call" not in lowered.as_text():
        raise AssertionError("long-T: no Mosaic call was lowered")
    jax.block_until_ready(compiled(q, k, v, ct, None))  # first run
    t0 = time.perf_counter()
    got = jax.block_until_ready(compiled(q, k, v, ct, None))
    wall = time.perf_counter() - t0
    if not all(np.all(np.isfinite(np.asarray(g, np.float32))) for g in got):
        raise AssertionError("long-T flash fwd+bwd is not finite")
    cases[f"long_T{T}"] = dict(fwd_bwd_s=round(wall, 4))
    return dict(compile_s=round(compile_s, 2), dtype=jnp.dtype(dtype).name,
                interpret=interpret, cases=cases)


# ---------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on whatever backend jax finds (CPU): "
                         "checks the script, says nothing about the chip")
    args = ap.parse_args(argv)

    import os

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    on_tpu = dev.platform == "tpu"
    if not args.rehearsal and not on_tpu:
        print(f"chip_smoke: jax found platform {dev.platform!r} "
              f"({dev.device_kind}), not a TPU — refusing to run. "
              f"(--rehearsal runs a tiny CPU walk-through of the script.)",
              file=sys.stderr)
        return 2

    from deeplearning4j_tpu.common import compile_cache
    from deeplearning4j_tpu.monitoring import RecompileWatchdog, compilecache

    env_cache_dir = os.environ.get(compile_cache.ENV_DIR)
    cache_dir = compile_cache.enable()
    watchdog = RecompileWatchdog().install()
    sizes = REHEARSAL if args.rehearsal else FULL
    stamp = {"platform": dev.platform, "device_kind": dev.device_kind,
             "device_count": device["count"], "jax": jax.__version__}
    if args.rehearsal:
        stamp["rehearsal"] = True
    emit({"phase": "start", **stamp, "compile_cache_dir": cache_dir,
          "compile_cache_from_env": env_cache_dir is not None})

    phases = {}
    t_all = time.perf_counter()

    def run(name, fn, *a):
        t0 = time.perf_counter()
        out = fn(sizes, on_tpu, *a)
        out["wall_s"] = round(time.perf_counter() - t0, 2)
        phases[name] = out
        emit({"phase": name, **stamp, **out})

    run("train", phase_train)
    run("serve", phase_serve)
    run("nn", phase_nn)
    run("kernels", phase_kernels)
    if device["count"] >= 4:
        run("mesh", phase_mesh, phases["train"]["losses"][0])

    cache = compilecache.stats()
    compiles = watchdog.stats()
    watchdog.close()
    if env_cache_dir is not None and \
            jax.config.jax_compilation_cache_dir != env_cache_dir:
        raise AssertionError(
            f"the program moved the compile cache: {compile_cache.ENV_DIR}="
            f"{env_cache_dir!r} but jax.config.jax_compilation_cache_dir="
            f"{jax.config.jax_compilation_cache_dir!r}")
    summary = {
        "phase": "summary", **stamp,
        "phases": {k: "pass" for k in phases},
        "compile_s": {k: v["compile_s"] for k, v in phases.items()},
        "wall_s": round(time.perf_counter() - t_all, 1),
        "compile_cache": {"dir": cache["dir"],
                          "hits": round(sum(cache["hits"].values())),
                          "misses": round(sum(cache["misses"].values())),
                          "xla_compiles": compiles["compiles"]},
    }
    summary["claim"] = None
    emit(summary)
    # the result line: exactly these keys, the device as jax reports it
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
