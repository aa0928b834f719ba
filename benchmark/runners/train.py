"""Runner for ``kind: train`` — one donated train step, on one chip or
under a ``SpecLayout`` mesh, timed in segments.

The seed reaches the device only as data: ``jax.random.key(seed)`` is an
ARGUMENT of the jitted init, every batch is a host array passed in. One
program per cell serves every seed, so every run after the first finds it in
the compile cache.
"""

from __future__ import annotations

import contextlib
import statistics
import time

import numpy as np

from benchmark import harness, work
from benchmark.models import transformer as family
from benchmark.reference import transformer as reference


class BatchMaker:
    """Host batches from the seed. The SET of sequence lengths is fixed by
    the traffic file (evenly spread between its bounds) and only permuted by
    the seed, so no seed changes the work of the masked kernels."""

    def __init__(self, traffic: dict, vocab: int, batch: int, seed: int):
        self.t = traffic
        self.vocab = vocab
        self.batch = batch
        self.seq = int(traffic["seq"])
        self.rs = np.random.RandomState(seed % (2 ** 32))
        lo = traffic.get("length_frac_min", 1.0)
        hi = traffic.get("length_frac_max", 1.0)
        self.lengths = np.rint(np.linspace(lo, hi, batch) * self.seq).astype(int)

    def make(self, batch: int = None) -> dict:
        rs, T = self.rs, self.seq
        B = batch or self.batch
        tokens = rs.randint(0, self.vocab, (B, T)).astype(np.int32)
        if self.t["objective"] == "causal_lm":
            labels = np.roll(tokens, -1, axis=1)
            weights = np.ones((B, T), np.float32)
            weights[:, -1] = 0.0  # the last position has no next token
            return {"tokens": tokens, "labels": labels.astype(np.int32),
                    "weights": weights}
        npos = int(self.t["mlm_positions"])
        lengths = rs.permutation(self.lengths)[:B]
        pad = (np.arange(T)[None, :] < lengths[:, None]).astype(np.float32)
        positions = np.stack([np.sort(rs.choice(int(n), npos, replace=False))
                              for n in lengths]).astype(np.int32)
        return {"tokens": tokens, "mlm_positions": positions,
                "labels": rs.randint(0, self.vocab, (B, npos)).astype(np.int32),
                "weights": np.ones((B, npos), np.float32), "pad_mask": pad}


def make_reference(model: dict, forward_only: bool):
    """ONE jitted program of the plain reference on a batch: (loss,) or
    (loss, gradient norm), at "highest" matmul precision. Weights and batch
    are arguments."""
    import jax

    def ref(params, batch):
        with jax.default_matmul_precision("highest"):
            if forward_only:
                return (reference.loss(params, batch, model),)
            loss, grads = jax.value_and_grad(reference.loss)(params, batch, model)
            return loss, reference.global_norm(grads)

    return jax.jit(ref)


def run(ctx: harness.Context) -> dict:
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import make_train_step
    from deeplearning4j_tpu.nn.updaters import Adam

    t, clock = ctx.traffic, ctx.clock
    model = ctx.config["model"]
    cfg = family.build_config(ctx.config, on_tpu=ctx.on_tpu,
                              causal=t["objective"] == "causal_lm",
                              max_len=max(int(t["seq"]), 1))
    model = {**model, "causal": cfg.causal}
    updater = Adam(float(t["learning_rate"]))
    layout = t.get("layout")
    replicas = int(layout["data"]) if layout else 1
    batch = int(t["batch_per_replica"]) * replicas
    seq = int(t["seq"])
    chips = len(ctx.devices)
    maker = BatchMaker(t, cfg.vocab_size, batch, ctx.seed)

    # -- placement: one chip, or the layout's mesh --------------------------
    init = family.make_init(cfg)
    mesh = None
    init_kw, state_kw, step_kw = {}, {}, {}
    if layout:
        from jax.sharding import PartitionSpec

        from deeplearning4j_tpu.parallel.partition import Partitioner, SpecLayout
        from deeplearning4j_tpu.parallel.sharding import batch_sharding

        spec_layout = SpecLayout(**layout)
        partitioner = Partitioner(spec_layout,
                                  mesh=spec_layout.build_mesh(ctx.devices))
        mesh = partitioner.mesh
        # specs need shapes only: weights are BORN sharded, never placed
        # through the host
        p_shapes = jax.eval_shape(init, jax.random.key(0))
        s_shapes = jax.eval_shape(updater.init, p_shapes)
        dummy = jax.tree.map(lambda s: np.broadcast_to(np.float32(0), s.shape),
                             (p_shapes, s_shapes))
        p_specs = partitioner.spec_tree(dummy[0])
        s_specs = Partitioner.state_spec_tree(dummy[1], p_specs)
        keep = jax.tree.map(partitioner.sharding_for, (p_specs, s_specs),
                            is_leaf=lambda x: isinstance(x, PartitionSpec))
        init_kw["out_shardings"], state_kw["out_shardings"] = keep
        # new state keeps the placement of the old (chip_smoke.py): left to
        # itself GSPMD hands 1-D leaves back under another spec
        step_kw["out_shardings"] = (*keep, None)
        put = lambda b: jax.device_put(b, batch_sharding(mesh))  # noqa: E731
    else:
        put = jax.device_put

    def in_mesh():
        return jax.sharding.set_mesh(mesh) if mesh is not None else contextlib.nullcontext()

    # -- weights: one jitted call, the key traced ---------------------------
    with in_mesh():
        params = jax.block_until_ready(
            jax.jit(init, **init_kw)(jax.random.key(ctx.seed)))
    clock.mark("weights")

    # -- the reference on the FIRST batch, before the step consumes the weights
    ck = t["check"]
    forward_only = bool(ck.get("forward_only"))
    batch0 = put(maker.make())
    with in_mesh():
        ref = [float(x) for x in make_reference(model, forward_only)(params, batch0)]
    clock.mark("reference")
    # the optimizer's state only now: the reference's float32 gradients are gone
    with in_mesh():
        opt = jax.block_until_ready(jax.jit(updater.init, **state_kw)(params))
    clock.mark("optimizer_state")

    # -- the one train step: compile or load, then warm up ------------------
    step = jax.jit(make_train_step(cfg, updater), donate_argnums=(0, 1), **step_kw)
    rng = jax.random.key(ctx.seed)
    it = 0

    def one_step(batch=None):
        nonlocal params, opt, it
        params, opt, loss = step(params, opt,
                                 put(maker.make()) if batch is None else batch,
                                 jnp.asarray(it, jnp.int32), rng)
        it += 1
        return loss

    with in_mesh():
        first_loss = float(one_step(batch0))
        clock.mark("compile_or_load")

        # -- the check: the measured step ITSELF against the reference. Its
        # first loss is the loss on the first batch; after one Adam step from
        # zero moments the first moment is (1 - beta1) x the gradient, so the
        # step's own gradient norm is |m| / (1 - beta1): no second program
        loss_err = abs(first_loss - ref[0]) / abs(ref[0])
        correct = bool(np.isfinite(first_loss) and loss_err <= ck["loss_rtol"])
        check_line = {"line": "check", "loss_system": first_loss,
                      "loss_reference": ref[0], "loss_rel_err": loss_err,
                      "loss_rtol": ck["loss_rtol"]}
        if not forward_only:
            gn = float(jax.jit(reference.global_norm)(opt["m"])) / (1.0 - updater.beta1)
            gn_err = abs(gn - ref[1]) / abs(ref[1])
            correct = correct and gn_err <= ck["grad_norm_rtol"]
            check_line.update(grad_norm_system=gn, grad_norm_reference=ref[1],
                              grad_norm_rel_err=gn_err,
                              grad_norm_rtol=ck["grad_norm_rtol"])
        ctx.emit({**check_line, "correct": correct})
        clock.mark("check")

        t0 = time.perf_counter()
        for _ in range(2):
            loss = one_step()
        loss.block_until_ready()
        step_s = (time.perf_counter() - t0) / 2
        clock.mark("warmup")

        # -- the window: segments of k steps, each ended by the loss --------
        k = max(1, int(round(float(t["segment_seconds"]) / step_s)))
        tokens_per_step = batch * seq
        tracer = harness.TracedWindow(ctx) if ctx.trace else None
        traced = range(1, 1 + int(t.get("trace_segments", 1)))
        segments, losses = [], []
        ctx.counters.open_window()
        setup_s = clock.setup_s()
        w0 = time.perf_counter()
        while (time.perf_counter() - w0 < ctx.seconds
               or len(segments) < int(t["min_segments"])):
            i = len(segments)
            if tracer and i == traced[0]:
                tracer.start()
                span = harness.annotate("bench:window")
                span.__enter__()
            s0 = time.perf_counter()
            for _ in range(k):
                with harness.annotate("bench:dispatch_step"):
                    loss = one_step()
            with harness.annotate("bench:wait_for_loss"):
                loss.block_until_ready()
            segments.append(time.perf_counter() - s0)
            losses.append(float(loss))
            if tracer and i == traced[-1]:
                span.__exit__(None, None, None)
                tracer.stop()
        window_s = time.perf_counter() - w0
        ctx.counters.close_window()

    steps = k * len(segments)
    finite = bool(np.all(np.isfinite(losses)))
    median_rate = tokens_per_step * k / statistics.median(segments) / chips
    window_rate = tokens_per_step * steps / window_s / chips
    ctx.emit({"line": "window", "steps": steps, "steps_per_segment": k,
              "segments": len(segments), "window_s": window_s,
              "segment_s_min": min(segments), "segment_s_max": max(segments),
              "items_s_chip_whole_window": window_rate,
              "items_s_chip_median_segment": median_rate,
              "first_loss": first_loss, "last_loss": losses[-1],
              "batch": batch, "seq": seq, "chips": chips,
              "rehearse": ctx.rehearse})

    flops_step = work.train_flops_per_step(
        model, batch=batch, seq=seq, head_positions=t.get("mlm_positions"))
    return {
        "correct": correct and finite,
        "attempted": steps, "failed": 0 if finite else steps,
        "end_to_end": {"setup_s": setup_s, "train_items_s_chip": window_rate},
        "trace": tracer.reduce() if tracer else None,
        "train": {"items_s_chip_median_segment": median_rate,
                  "items_s_chip_whole_window": window_rate,
                  "flops_per_item": flops_step / tokens_per_step,
                  "causal": cfg.causal},
        "counters": ctx.counters.summary(),
        "memory": {"peak": harness.memory_peak_bytes(ctx.devices),
                   "limit": harness.memory_limit_bytes(ctx.devices)}
        if ctx.on_tpu else None,
        "peaks": ctx.peaks,
    }
