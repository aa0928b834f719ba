"""The ``kimi_k2`` family: a configuration file (the published key names at
its top level, the chip's share under ``model``) to the program's own
``KimiK2Config``, to weights made on the device, and the comparison with the
reference that decides ``correct`` for a served cell."""

from __future__ import annotations

import numpy as np

from benchmark import loadgen
from benchmark.reference import kimi_k2 as reference


def build_config(config: dict, *, on_tpu: bool, max_len=None):
    """``models.kimi_k2.KimiK2Config`` as the cell runs it."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.kimi_k2 import KimiK2Config

    rs, share = config["rope_scaling"], config["model"]
    return KimiK2Config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        first_k_dense_replace=config["first_k_dense_replace"],
        num_attention_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"], kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        n_routed_experts=share["router_width"],
        expert_first=share["expert_first"],
        n_resident_experts=config["n_routed_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        routed_scaling_factor=config["routed_scaling_factor"],
        rms_norm_eps=config["rms_norm_eps"], rope_theta=float(config["rope_theta"]),
        rope_factor=float(rs["factor"]),
        rope_original_max=rs["original_max_position_embeddings"],
        rope_beta_fast=float(rs["beta_fast"]), rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale=float(rs["mscale"]), rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        max_position_embeddings=max_len or config["max_position_embeddings"],
        param_dtype=jnp.dtype(share["param_dtype"]),
        # off the chip "auto" picks the dense path; a rehearsal names the
        # kernel so the flash route still runs (interpreted)
        attn_impl="auto" if on_tpu else "flash")


def reference_model(config: dict) -> dict:
    """What the reference reads: the published keys, and the chip's share."""
    return {**{k: v for k, v in config.items() if not isinstance(v, (list, str))},
            "expert_first": config["model"]["expert_first"],
            "n_resident_experts": config["n_routed_experts"]}


def make_init(cfg):
    """The function of the KEY that makes every weight: jit it once, so the
    seed reaches the device as data and one program serves every seed."""
    from deeplearning4j_tpu.models.kimi_k2 import init_params

    return lambda key: init_params(key, cfg)


CONTROLS = ("fp8_experts", "drop_expert")


def control_params(params, control: str):
    """The weights a CONTROL run serves: ``params`` with one fault in the
    first sparse layer, every other leaf shared. ``fp8_experts`` rounds that
    layer's resident experts through float8_e4m3 (the nearest precision below
    the configuration's bfloat16); ``drop_expert`` zeroes its first resident
    expert's way out. The reference keeps the sound weights, so the check has
    to come out NOT correct (``runners/serve_family.py`` reads the control's
    name from ``BENCHMARK_CHECK_CONTROL`` and stops after the check)."""
    import jax.numpy as jnp

    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r} (have: {CONTROLS})")
    at = next(i for i, p in enumerate(params["layers"]) if "experts" in p)
    p = params["layers"][at]
    if control == "fp8_experts":
        experts = [{k: w.astype(jnp.float8_e4m3fn).astype(w.dtype)
                    for k, w in e.items()} for e in p["experts"]]
    else:
        experts = [{**p["experts"][0], "wd": jnp.zeros_like(p["experts"][0]["wd"])},
                   *p["experts"][1:]]
    layers = list(params["layers"])
    layers[at] = {**p, "experts": experts}
    return {**params, "layers": layers}


def shapes(cfg, *, slots: int, block_T: int) -> dict:
    """What the work functions (``benchmark/work_kimi_k2.py``) count from."""
    from deeplearning4j_tpu.models.kimi_k2 import is_sparse

    L = cfg.num_hidden_layers
    return {"hidden": cfg.hidden_size, "heads": cfg.num_attention_heads,
            "q_rank": cfg.q_lora_rank, "kv_rank": cfg.kv_lora_rank,
            "nope": cfg.qk_nope_head_dim, "rope": cfg.qk_rope_head_dim,
            "v_dim": cfg.v_head_dim, "dense_width": cfg.intermediate_size,
            "expert_width": cfg.moe_intermediate_size,
            "router_width": cfg.n_routed_experts,
            "resident_experts": cfg.n_resident_experts,
            "experts_per_token": cfg.num_experts_per_tok,
            "layers": L, "sparse_layers": sum(is_sparse(cfg, l) for l in range(L)),
            "vocab": cfg.vocab_size, "slots": slots, "block_T": block_T,
            "weight_bytes": int(np.dtype(cfg.param_dtype).itemsize)}


def make_reference(cfg, model: dict):
    """The reference, layer by layer so that it fits beside the served
    weights: (tokens -> hidden, a dense block, a sparse block taken apart,
    hidden -> logits), each one jitted program whatever the layer."""
    import jax

    def highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    return (highest(reference.embed),
            highest(lambda p, h: reference.block(p, h, model)),
            highest(lambda p, h: reference.sparse_block_parts(p, h, model)),
            highest(lambda params, h: reference.logits(params, h, model)))


def check_served_path(ctx, pool, cfg, params, rs, reference_params=None) -> bool:
    """Prefill then decode through the paged latent cache against the
    reference's full forward (two prompts, a few steps each, decoded
    TOGETHER with ``bystander_lens`` further live slots and the pool's dead
    ones beside them), and every sparse layer's routing and resident experts
    against the reference's ON THE REFERENCE'S INPUT, at the prefill
    program's shape and at the decode program's. Logits decide, not tokens.
    ``params`` is what is served; the reference reads ``reference_params``
    (the same, but for a control run: :func:`control_params`).

    bf16 hidden states against float32 ones differ by about a hundredth,
    enough to carry a resident expert across the boundary between the k-th
    and the (k+1)-th score at a few positions in a hundred, and such a
    position's logits then differ by a tenth of the largest (PERF.md, PR
    31). So what runs END TO END is held by statistics that a few such
    positions do not move, and each layer's own arithmetic is held exactly:

    (a) served: of the tokens the pool chose, ``served_min_share`` lie
        within ``argmax_gap_rtol`` x max|logit| of the reference's largest
        logit at their position (a wrong cache row, position or kernel puts
        every one of them several standard deviations below);
    (b) forward: over positions, the MEDIAN and the 90th percentile of the
        program's full forward's error, max over the vocabulary, stay under
        ``logit_median_rtol`` and ``logit_p90_rtol`` x max|logit| (the
        maximum is printed);
    (c) routing, given the reference's expert-layer input: the chosen sets
        are EQUAL wherever the reference's boundary margin is at least
        ``route_margin_eps``; at most ``set_aside_max_share`` of the
        positions may lie under it;
    (d) experts, given the reference's input AND routing: the resident
        experts' part of the layer matches the reference's to
        ``expert_rtol`` x its largest value (fp8 or int8 expert weights, or
        an expert left out, fail here), over all checked positions at once
        (the prefill program's rows and tile) and again in groups of
        ``pool.slots`` rows (a decode step's rows and its short tile)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.kimi_k2 import forward, resident_experts, route

    ck = ctx.traffic["check"]
    steps = int(ck["decode_steps"])
    reference_params = params if reference_params is None else reference_params
    model = reference_model(ctx.config)
    width = max(ck["prompt_lens"]) + steps
    held = []  # (prompt, slot, tokens chosen): the compared prompts first
    for n in (*ck["prompt_lens"], *ck["bystander_lens"]):
        prompt = loadgen.prompt_tokens(rs, int(n), cfg.vocab_size)
        slot, first = pool.admit(prompt, steps + 1)
        held.append((prompt, slot, [int(first)]))
    for _ in range(steps):
        out = pool.step()
        for _, slot, chosen in held:
            chosen.extend(int(x) for x in out[slot])
    for _, slot, _ in held:
        pool.release(slot)
    rows, served = [], []
    for prompt, _, chosen in held[:len(ck["prompt_lens"])]:
        n = len(prompt)
        seq = np.zeros(width, np.int32)
        seq[:n] = prompt
        seq[n:n + steps] = chosen[:steps]  # teacher-forced with the pool's tokens
        rows.append(seq)
        served.append((n, chosen[:steps + 1]))
    tokens = jnp.asarray(np.stack(rows))
    real = np.zeros(tokens.shape, bool)
    for r, (n, _) in enumerate(served):
        real[r, :n + steps] = True
    # the checked positions again, in groups of a decode step's rows (what is
    # left over after the last whole group is held by the full comparison)
    at = np.flatnonzero(real.reshape(-1))
    at = at[:len(at) // pool.slots * pool.slots].reshape(-1, pool.slots)

    @jax.jit
    def system_layer(p, u, idx, w):
        """The program's routing of rows ``u`` [N, D], and its resident
        experts' part under the routing it is GIVEN."""
        part, _ = resident_experts(cfg, p, u.astype(cfg.param_dtype), idx, w,
                                   jnp.ones(u.shape[0], bool))
        return route(cfg, p, u)[0], part

    embed, dense_block, sparse_parts, logits_of = make_reference(cfg, model)
    h = embed(reference_params, tokens)
    under = mismatched = compared = 0
    expert_err, step_err = 0.0, 0.0
    resident_margin = np.full(tokens.shape, np.inf)
    flat_real = real.reshape(-1)
    for p, ref_p in zip(params["layers"], reference_params["layers"]):
        if "experts" not in p:
            h = dense_block(ref_p, h)
            continue
        ref = sparse_parts(ref_p, h)
        u, idx, w, routed = (np.asarray(ref[k]).reshape(flat_real.size, -1)
                             for k in ("u", "idx", "w", "routed"))
        sys_idx, sys_part = system_layer(p, u, idx, w)
        kept = flat_real & (np.asarray(ref["boundary"]).reshape(-1)
                            >= float(ck["route_margin_eps"]))

        def same(a, b):
            return (np.sort(np.asarray(a), -1) == np.sort(b, -1)).all(-1)

        under += int((flat_real & ~kept).sum())
        mismatched += int((kept & ~same(sys_idx, idx)).sum())
        compared += int(flat_real.sum())
        top = np.abs(routed[flat_real]).max()
        expert_err = max(expert_err, float(
            np.abs(np.asarray(sys_part) - routed)[flat_real].max() / top))
        for rows in at:
            step_idx, step_part = system_layer(p, u[rows], idx[rows], w[rows])
            mismatched += int((kept[rows] & ~same(step_idx, idx[rows])).sum())
            step_err = max(step_err, float(
                np.abs(np.asarray(step_part) - routed[rows]).max() / top))
        resident_margin = np.minimum(resident_margin, np.asarray(ref["resident"]))
        h = ref["out"]
    ref_logits = np.asarray(logits_of(reference_params, h))
    system = np.asarray(jax.jit(lambda p, t: forward(p, t, cfg))(params, tokens),
                        np.float32)

    scale = np.abs(ref_logits[real]).max()
    per_position = (np.abs(system - ref_logits).max(-1) / scale)[real]
    gaps = []
    for r, (n, chosen) in enumerate(served):
        for j, tok in enumerate(chosen):  # token j was read at position n-1+j
            row = ref_logits[r, n - 1 + j]
            gaps.append(float((row.max() - row[tok]) / np.abs(row).max()))
    within = float(np.mean(np.asarray(gaps) <= ck["argmax_gap_rtol"]))
    line = {
        "served_tokens_checked": len(gaps), "served_share_within_gap": within,
        "served_min_share": ck["served_min_share"], "argmax_gap_max": max(gaps),
        "argmax_gap_rtol": ck["argmax_gap_rtol"], "slots_live_together": len(held),
        "forward_err_median": float(np.median(per_position)),
        "logit_median_rtol": ck["logit_median_rtol"],
        "forward_err_p90": float(np.quantile(per_position, 0.9)),
        "logit_p90_rtol": ck["logit_p90_rtol"],
        "forward_err_max": float(per_position.max()),
        "positions": int(real.sum()),
        "positions_a_resident_expert_within_0.01_of_the_boundary": int(
            (resident_margin[real] < 0.01).sum()),
        "routing_compared": compared, "routing_set_aside": under,
        "routing_set_aside_share": under / compared,
        "set_aside_max_share": ck["set_aside_max_share"],
        "route_margin_eps": ck["route_margin_eps"], "routing_mismatched": mismatched,
        "expert_part_rel_err": expert_err, "decode_rows": int(at.shape[1]),
        "decode_row_groups": int(at.shape[0]),
        "expert_part_rel_err_decode_rows": step_err, "expert_rtol": ck["expert_rtol"]}
    ok = bool(within >= ck["served_min_share"]
              and line["forward_err_median"] <= ck["logit_median_rtol"]
              and line["forward_err_p90"] <= ck["logit_p90_rtol"]
              and mismatched == 0
              and line["routing_set_aside_share"] <= ck["set_aside_max_share"]
              and max(expert_err, step_err) <= ck["expert_rtol"])
    ctx.emit({"line": "check", **line, "correct": ok})
    return ok
