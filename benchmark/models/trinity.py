"""The ``trinity`` family: a configuration file (the published key names at its
top level, the chip's share under ``model``) to the program's own
``TrinityConfig``, to weights made on the device, and the comparison with the
reference that decides ``correct`` for a served cell."""

from __future__ import annotations

import dataclasses
import functools
import os

import numpy as np

from benchmark import loadgen
from benchmark.reference import trinity as reference

#: faults a control run serves (``BENCHMARK_CHECK_CONTROL``): three facts of
#: the equations switched in the program's configuration, two faults of the
#: attention's reach made of its family, two in the weights (``control_params``)
CONFIG_CONTROLS = {
    "window_off_by_one": lambda cfg: {"sliding_window": cfg.sliding_window + 1},
    "rope_on_global": lambda cfg: {"rope_on_full_attention": True},
    "no_gate": lambda cfg: {"attention_gate": False},
}
REACH_CONTROLS = ("no_window", "read_freed_block")
WEIGHT_CONTROLS = ("drop_expert", "fp8_experts")
CONTROLS = (*CONFIG_CONTROLS, *REACH_CONTROLS, *WEIGHT_CONTROLS)


def _faulty_reach(cfg, control: str):
    """``cfg`` whose attention reaches further than a sliding layer's window;
    the pool's cache groups stay as they are. ``no_window``: a sliding layer
    attends everything under the causal mask, in prefill and in a step (which
    then looks a freed block up in its table). ``read_freed_block``: prefill is
    sound, a step's sliding layers begin one block before the first key the
    query sees: the block the pool has just handed back."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.kernels.paged_attention import paged_decode_attention
    from deeplearning4j_tpu.models.trinity import WindowedGQADecodeFamily

    class OneBlockEarly(WindowedGQADecodeFamily):
        def attend_step(self, q, k_arena, v_arena, table, limits, layer):
            c, window = self.cfg, self.cfg.window_of(layer)
            starts = (None if window is None
                      else jnp.maximum(limits - window - k_arena.shape[2], 0))
            return paged_decode_attention(
                q, k_arena, v_arena, table, limits, layer=self.place[layer][1],
                n_heads=c.num_attention_heads, kv_heads=c.num_key_value_heads,
                starts=starts)

    class FaultyConfig(type(cfg)):
        if control == "no_window":
            def window_of(self, layer):
                return None   # the cache groups go by layer_types: they stand
        else:
            def decode_family(self):
                return OneBlockEarly(self)

    return FaultyConfig(**{f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)})


def build_config(config: dict, *, on_tpu: bool, max_len=None):
    """``models.trinity.TrinityConfig`` as the cell runs it; in a control run
    that names a fault of behaviour, with that fault."""
    import jax.numpy as jnp

    from benchmark.runners.serve_family import CONTROL_ENV
    from deeplearning4j_tpu.models.trinity import TrinityConfig

    share = config["model"]
    if not (config["route_norm"] and config["score_func"] == "sigmoid"
            and config["num_shared_experts"] == 1
            and config["n_group"] == config["topk_group"] == 1):
        raise ValueError("the expert layer is sigmoid scores normalised over the "
                         "chosen, one group, one shared expert: another setting "
                         "is not implemented")
    L = config["num_hidden_layers"]
    cfg = TrinityConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=L, num_dense_layers=config["num_dense_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_experts=share["router_width"], expert_first=share["expert_first"],
        n_resident_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        route_scale=config["route_scale"], rms_norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        sliding_window=config["sliding_window"],
        global_attn_every_n_layers=config["global_attn_every_n_layers"],
        # the published list, as far as the cut is deep
        layer_types=tuple(config["layer_types"][:L]),
        mup_enabled=config["mup_enabled"],
        max_position_embeddings=max_len or config["max_position_embeddings"],
        param_dtype=jnp.dtype(share["param_dtype"]),
        **({"prefill_chunk": share["prefill_chunk"]} if "prefill_chunk" in share else {}),
        # off the chip "auto" picks the dense path; a rehearsal names the
        # kernel so the windowed flash route still runs (interpreted)
        attn_impl="auto" if on_tpu else "flash")
    control = os.environ.get(CONTROL_ENV)
    if control in CONFIG_CONTROLS:
        cfg = dataclasses.replace(cfg, **CONFIG_CONTROLS[control](cfg))
    elif control in REACH_CONTROLS:
        cfg = _faulty_reach(cfg, control)
    return cfg


def reference_model(config: dict) -> dict:
    """What the reference reads: the published keys, the cut's layer kinds and
    the chip's share."""
    return {**{k: v for k, v in config.items() if not isinstance(v, (list, str, dict))},
            "layer_types": tuple(config["layer_types"][:config["num_hidden_layers"]]),
            "expert_first": config["model"]["expert_first"]}


def make_init(cfg):
    """The function of the KEY that makes every weight: jit it once, so the
    seed reaches the device as data and one program serves every seed."""
    from deeplearning4j_tpu.models.trinity import init_params

    return lambda key: init_params(key, cfg)


def control_params(params, control: str):
    """The weights a CONTROL run serves. ``fp8_experts`` rounds the first
    expert layer's resident experts through float8_e4m3 (the nearest precision
    below the configuration's bfloat16); ``drop_expert`` zeroes its first
    resident expert's way out; every other leaf is shared. The faults of
    behaviour (``build_config``) serve the sound weights. The reference keeps
    the sound weights and the published equations, so the check has to come
    out NOT correct (``runners/serve_family.py`` stops after it)."""
    import jax.numpy as jnp

    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r} (have: {CONTROLS})")
    if control not in WEIGHT_CONTROLS:
        return params
    at = next(i for i, p in enumerate(params["layers"]) if "experts" in p)
    p = params["layers"][at]
    if control == "fp8_experts":
        experts = {k: w.astype(jnp.float8_e4m3fn).astype(w.dtype)
                   for k, w in p["experts"].items()}
    else:
        experts = {**p["experts"], "wd": p["experts"]["wd"].at[0].set(0)}
    layers = list(params["layers"])
    layers[at] = {**p, "experts": experts}
    return {**params, "layers": layers}


def shapes(cfg, *, slots: int, block_T: int) -> dict:
    """What the work functions (``benchmark/work_trinity.py``) count from."""
    from deeplearning4j_tpu.models.trinity import FULL, is_sparse

    L = cfg.num_hidden_layers
    return {"hidden": cfg.hidden_size, "heads": cfg.num_attention_heads,
            "kv_heads": cfg.num_key_value_heads, "head_dim": cfg.head_dim,
            "window": cfg.sliding_window, "dense_width": cfg.intermediate_size,
            "expert_width": cfg.moe_intermediate_size,
            "router_width": cfg.num_experts,
            "resident_experts": cfg.n_resident_experts,
            "experts_per_token": cfg.num_experts_per_tok,
            "layers": L, "full_layers": sum(k == FULL for k in cfg.layer_types),
            "sparse_layers": sum(is_sparse(cfg, l) for l in range(L)),
            "vocab": cfg.vocab_size, "slots": slots, "block_T": block_T,
            "weight_bytes": int(np.dtype(cfg.param_dtype).itemsize)}


def _highest(fn, **jit_kw):
    import jax

    def run(*args, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kw)
    return jax.jit(run, **jit_kw)


LOUD = 1024.0  # a loud value row: what one key more or less moves the output by


def make_compare(cfg, model: dict, q_block: int):
    """The jitted pieces of the layer-by-layer comparison, each one program a
    KIND of layer: the reference's block taken apart, and the program's
    attention and experts ON THE REFERENCE'S INPUT, reduced on the device to
    the few numbers the check reads."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import trinity as tr
    from deeplearning4j_tpu.models.kimi_k2 import resident_experts, route

    family = cfg.decode_family()
    # the reference's block as TWO programs, and of each what the check reads
    # and no more: their temporaries lie in the chip's memory beside the
    # served weights and the pool's arenas (one program of a whole float32
    # block wanted 3.5 GB where 3 were free)
    def ref_attention(p, h, kind):
        att = reference.attention_parts(p, h, model, kind, q_block)
        return {k: att[k] for k in ("q", "k", "v", "branch", "out")}

    ref_block = (_highest(ref_attention, static_argnums=2),
                 _highest(lambda p, h: reference.ffn_parts(p, h, model, q_block)))

    def rel(got, want, rows):
        """max |got - want| over ``rows`` as a share of the largest wanted."""
        want = jnp.where(rows[..., None], want, 0.0)
        got = jnp.where(rows[..., None], got.astype(jnp.float32), 0.0)
        return jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))

    def loud_values(v, loud):
        """``v`` [T, G, d] with the rows ``loud`` [T] made LOUD (the sign by
        the lane's parity: exact in any dtype)."""
        sign = jnp.where(jnp.arange(v.shape[-1]) % 2 == 0, LOUD, -LOUD)
        return jnp.where(loud[:, None, None], sign.astype(v.dtype), v)

    @functools.partial(jax.jit, static_argnames=("layer",))
    def attention(p, h, att, real, loud, *, layer):
        """The program's attention of layer ``layer`` on the reference's
        residual ``h`` [1, T, D]: (the branch that joins the residual against
        the reference's; the kernel's output with LOUD value rows at ``loud``
        [T] against the reference's attention of its own q and k over the same
        values: one key more or less at a window's edge moves a query's
        output by about its size)."""
        T = h.shape[1]
        pos = jnp.arange(T, dtype=jnp.int32)[None]
        q, k, v = tr.attention_rows(cfg, p, layer, h, pos)
        branch = tr.attention_branch(cfg, p, h, tr.attend(cfg, q, k, v, cfg.window_of(layer)))
        o_loud = tr.attend(cfg, q, k, loud_values(v[0], loud)[None], cfg.window_of(layer))
        kind = model["layer_types"][layer]
        with jax.default_matmul_precision("highest"):
            want = reference.attend(
                att["q"], att["k"], loud_values(att["v"][0], loud)[None],
                model["sliding_window"] if kind == reference.SLIDING else None, q_block)
        return rel(branch, att["branch"], real), rel(o_loud, want, real)

    @functools.partial(jax.jit, static_argnames=("layer", "block_T", "max_len"))
    def decode_step(p, h, att, at, live, loud, *, layer, block_T, max_len):
        """ONE layer of the decode step's own attention (``family.attend_step``,
        the function ``decode_window`` calls, at the pool's slots, ``block_T``
        and ``max_len``) over arenas that hold the REFERENCE's rows of this
        layer, LOUD at ``loud``: slot i is a query at position ``at[i]`` of
        the reference's sequence (``live[i]`` false: a dead slot, which maps
        nothing). The tables are what the pool keeps for such a slot by the
        PUBLISHED window: a block behind it is unmapped, and the trash block
        such an entry points at holds values ten times LOUDER. Against float32
        attention of the reference's own q and k, a query's error over ITS
        largest value."""
        kind = model["layer_types"][layer]
        window = model["sliding_window"] if kind == reference.SLIDING else None
        g, place = family.place[layer]
        T = h.shape[1]
        n_blocks = -(-T // block_T)
        v_loud = loud_values(att["v"][0], loud)

        def arena(rows, trash):
            """[L_group, 1 + n_blocks, block_T, lanes]: block j + 1 of THIS
            layer holds positions j * block_T ..; block 0 is the trash block."""
            rows = rows.reshape(T, -1).astype(cfg.param_dtype)
            rows = jnp.pad(rows, ((0, n_blocks * block_T - T), (0, 0)))
            blocks = jnp.concatenate([jnp.full((1, block_T, rows.shape[-1]), trash, rows.dtype),
                                      rows.reshape(n_blocks, block_T, -1)])
            whole = jnp.zeros((family.cache_groups[g].n_layers, *blocks.shape), rows.dtype)
            return whole.at[place].set(blocks)

        k_arena, v_arena = arena(att["k"][0], 1.0), arena(v_loud, 10 * LOUD)
        blocks = jnp.arange(max_len // block_T, dtype=jnp.int32)[None, :]
        first = 0 if window is None else jnp.maximum(at - window + 1, 0) // block_T
        mapped = live[:, None] & (blocks * block_T <= at[:, None]) & (
            blocks >= jnp.reshape(first, (-1, 1)))
        table = jnp.where(mapped, blocks + 1, 0)
        limits = jnp.where(live, at + 1, 0)[:, None]
        q = tr.attention_rows(cfg, p, layer, h[0, at][:, None], at[:, None])[0]
        o = family.attend_step(q.reshape(at.shape[0], 1, -1), k_arena, v_arena, table,
                               limits, layer)[:, 0]
        with jax.default_matmul_precision("highest"):
            want = reference.attend_at(att["q"][0, at], at, att["k"][0], v_loud, window)
        err = jnp.max(jnp.abs(o.astype(jnp.float32) - want), -1) / jnp.max(jnp.abs(want), -1)
        return jnp.max(jnp.where(live, err, 0.0))

    @jax.jit
    def experts(p, u, idx, w):
        """The program's routing of rows ``u`` [N, D], and its resident
        experts' part under the routing it is GIVEN."""
        part, _ = resident_experts(cfg, p, u.astype(cfg.param_dtype), idx, w,
                                   jnp.ones(u.shape[0], bool))
        return route(cfg, p, u)[0], part

    @jax.jit
    def rows_error(cached, want):
        """A cached row against the reference's, a row: max |difference| over
        the largest value any row has. cached [n, lanes], want [n, G, d]."""
        want = want.reshape(want.shape[0], -1)
        return (jnp.max(jnp.abs(cached.astype(jnp.float32) - want), axis=-1)
                / jnp.max(jnp.abs(want)))

    return ref_block, attention, decode_step, experts, rows_error


def check_served_path(ctx, pool, cfg, params, rs, reference_params=None) -> bool:
    """Prefill then decode through the pool's two cache groups against the
    reference's full forward (``prompt_lens``, ``decode_steps`` steps each,
    decoded TOGETHER with ``bystander_lens`` further live slots and the pool's
    dead ones; the longest compared prompt is past the window and its steps
    cross a block boundary of it, so a block is handed back while it is
    compared), and every layer's attention, cache rows, routing and experts
    against the reference's ON THE REFERENCE'S INPUT. Logits decide, not
    tokens. ``params`` is what is served; the reference reads
    ``reference_params`` (the same, but for a control run) and the PUBLISHED
    equations, whatever ``cfg`` says.

    bf16 hidden states differ from float32 ones by about a hundredth, which
    carries an expert across the router's top-k boundary at a few positions in
    a hundred (PERF.md, PR 31). So what runs END TO END is held by medians and
    shares, and each layer's own arithmetic on the reference's input:

    (a) served: of the tokens the pool chose, ``served_min_share`` lie within
        ``argmax_gap_rtol`` x max|logit| of the reference's largest logit;
    (b) forward: over a sample of positions, the MEDIAN and the 90th
        percentile of the program's full forward's error, max over the
        vocabulary, stay under ``logit_median_rtol`` / ``logit_p90_rtol``;
    (c) cache: what the arenas of BOTH groups hold of the compared slots after
        the steps matches the reference's K and V rows — of the sliding group
        the rows the last query still sees: in the FIRST layer, whose input
        is the embedding in both, every row, prefilled or written by a step,
        to ``cache_first_layer_rtol``; in every layer the median row of a
        prompt to ``cache_median_rtol`` and every row a step wrote to
        ``cache_step_rtol``. The pool has to have FREED a block of the sliding
        group during the compared steps, and the table of the slot past the
        window maps exactly the blocks its last query sees;
    (d) attention, given the reference's residual: the branch that joins the
        residual (norms, rotary or none, the window, the gate, Wo, the norm
        after) to ``attend_rtol`` x the largest value; and the prefill kernel
        with LOUD value rows at the window's edges (``edge_rtol``: one key
        more or less at an edge moves a query's output by about its size);
    (e) the DECODE step's attention (``decode_step`` above: the function a
        step calls, on arenas that hold the reference's rows, the tables as
        the pool keeps them by the published window, the trash block louder
        still): to ``decode_edge_rtol`` x the query's own largest value;
    (f) routing, given the reference's expert-layer input: the chosen sets
        are EQUAL wherever the boundary margin is at least
        ``route_margin_eps`` (``set_aside_max_share`` may lie under it);
    (g) experts, given the reference's input AND routing: to ``expert_rtol`` x
        the largest value, over all rows at once (the prefill program's tile)
        and in groups of ``pool.slots`` rows (a decode step's short tile)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.trinity import prefill_forward

    ck = ctx.traffic["check"]
    steps = int(ck["decode_steps"])
    reference_params = params if reference_params is None else reference_params
    model = reference_model(ctx.config)
    window, bT = int(model["sliding_window"]), pool.block_T
    compared = len(ck["prompt_lens"])
    held = []  # (prompt, slot, tokens chosen): the compared prompts first
    for n in (*ck["prompt_lens"], *ck["bystander_lens"]):
        prompt = loadgen.prompt_tokens(rs, int(n), cfg.vocab_size)
        slot, first = pool.admit(prompt, steps + 1)
        held.append((prompt, slot, [int(first)]))
    freed = -pool.block_stats()["kv_window_blocks_freed"]
    for _ in range(steps):
        out = pool.step()
        for _, slot, chosen in held:
            chosen.extend(int(x) for x in out[slot])
    freed += pool.block_stats()["kv_window_blocks_freed"]
    cached = [pool.cached_rows(slot, len(prompt) + steps)
              for prompt, slot, _ in held[:compared]]
    # the sliding group's table of each compared slot: it maps exactly the
    # blocks that the last query (at n + steps - 1) sees
    table_faults = 0
    for prompt, slot, _ in held[:compared]:
        last = len(prompt) + steps - 1
        want = np.zeros(pool.max_blocks, bool)
        want[max(0, last - window + 1) // bT:last // bT + 1] = True
        table_faults += int(((pool.block_tables(slot)[1] > 0) != want).sum())
    for _, slot, _ in held:
        pool.release(slot)

    q_block = int(ck["reference_q_block"])  # a tile size of the reference, not semantics
    width = max(ck["prompt_lens"]) + steps
    width = -(-width // q_block) * q_block if width > q_block else width
    (ref_attention, ref_ffn), attention, decode_step, experts, rows_error = make_compare(
        cfg, model, q_block)
    embed = _highest(lambda params, tokens: reference.embed(params, tokens, model))
    logits_of = _highest(lambda params, h: reference.logits(params, h, model))
    system_hidden = jax.jit(lambda p, t: prefill_forward(p, t, cfg)[0])
    family = cfg.decode_family()

    def same(a, b):
        return (np.sort(np.asarray(a), -1) == np.sort(b, -1)).all(-1)

    gaps, per_position = [], []
    attend_err = edge_err = step_edge_err = 0.0
    under = mismatched = routed_rows = 0
    expert_err = step_err = 0.0
    cache_first, cache_median, cache_step = 0.0, 0.0, 0.0
    n_dead = max(min(2, pool.slots - 2), 0)
    live = jnp.arange(pool.slots) >= n_dead  # dead ones first: the order of the live matters
    # attention depends on a layer's KIND alone: the first layer of each kind
    # stands for it (its place in its group's arenas too), so a kind is one
    # compiled program
    canon = {kind: model["layer_types"].index(kind) for kind in set(model["layer_types"])}
    attn_keys = ("attn_norm", "wq", "wk", "wv", "wgate", "q_norm", "k_norm", "wo",
                 "post_attn_norm")
    for (prompt, _, chosen), rows in zip(held[:compared], cached):
        n = len(prompt)
        seq = np.zeros((1, width), np.int32)
        seq[0, :n] = prompt
        seq[0, n:n + steps] = chosen[:steps]  # teacher-forced with the pool's tokens
        tokens = jnp.asarray(seq)
        real = jnp.arange(width)[None, :] < n + steps
        flat_real = np.asarray(real).reshape(-1)
        groups_of_rows = np.flatnonzero(flat_real)
        groups_of_rows = groups_of_rows[:len(groups_of_rows) // pool.slots * pool.slots
                                        ].reshape(-1, pool.slots)[:8]
        # (e)'s queries: decode positions around the step that hands a block
        # back (the first p >= n with (p - window + 1) % block_T == 0)
        n_live = pool.slots - n_dead
        edge = n + (-(n - window + 1)) % bT
        start = int(np.clip(edge - n_live // 2, n, max(n, n + steps - n_live)))
        queries = np.concatenate([np.zeros(n_dead), np.minimum(
            start + np.arange(n_live), n + steps - 1)]).astype(np.int32)
        # LOUD value rows: the key just behind each of those queries' windows
        # (the first one inside the next query's)
        loud = np.zeros(width, bool)
        loud[np.asarray([p - window for p in queries[n_dead:] if p >= window], int)] = True
        loud, queries = jnp.asarray(loud), jnp.asarray(queries)
        last_seen = (n + steps - 1) - window + 1   # first key the last query sees
        h = embed(reference_params, tokens)
        for l, (p, ref_p) in enumerate(zip(params["layers"], reference_params["layers"])):
            kind = model["layer_types"][l]
            att = ref_attention(ref_p, h, kind)
            p_att = {k: p[k] for k in attn_keys}
            a_err, e_err = attention(p_att, h, att, real, loud, layer=canon[kind])
            attend_err, edge_err = max(attend_err, float(a_err)), max(edge_err, float(e_err))
            step_edge_err = max(step_edge_err, float(decode_step(
                p_att, h, att, queries, live, loud, layer=canon[kind], block_T=bT,
                max_len=pool.max_len)))
            g, place = family.place[l]
            for arena, name in ((2 * g, "k"), (2 * g + 1, "v")):
                err = np.asarray(rows_error(rows[arena][place], att[name][0, :n + steps]))
                if kind == reference.SLIDING:   # rows behind the window are gone
                    err = np.where(np.arange(n + steps) >= last_seen, err, 0.0)
                    first_row = max(0, last_seen)
                else:
                    first_row = 0
                if first_row < n:
                    cache_median = max(cache_median, float(np.median(err[first_row:n])))
                cache_step = max(cache_step, float(err[n:].max()))
                if l == 0:
                    cache_first = max(cache_first, float(err.max()))
            ref = ref_ffn(ref_p, att.pop("out"))
            del att
            if "idx" in ref:
                u, idx, w, routed = (np.asarray(ref[k]).reshape(width, -1)
                                     for k in ("u", "idx", "w", "routed"))
                sys_idx, sys_part = experts(p, u, idx, w)
                kept = flat_real & (np.asarray(ref["boundary"]).reshape(-1)
                                    >= float(ck["route_margin_eps"]))
                under += int((flat_real & ~kept).sum())
                mismatched += int((kept & ~same(sys_idx, idx)).sum())
                routed_rows += int(flat_real.sum())
                top = np.abs(routed[flat_real]).max()
                expert_err = max(expert_err, float(
                    np.abs(np.asarray(sys_part) - routed)[flat_real].max() / top))
                for group in groups_of_rows:
                    step_idx, step_part = experts(p, u[group], idx[group], w[group])
                    mismatched += int((kept[group] & ~same(step_idx, idx[group])).sum())
                    step_err = max(step_err, float(
                        np.abs(np.asarray(step_part) - routed[group]).max() / top))
            h = ref["out"]
            del ref
        # logits where the pool read its tokens, and at a sample of the prompt
        served_at = np.arange(n - 1, n + steps)
        sample = np.unique(np.concatenate([
            np.linspace(0, n - 2, int(ck["forward_positions"])).astype(int), served_at]))
        ref_logits = np.asarray(logits_of(reference_params, h[0, sample]))
        mine = np.asarray(family.head(
            params, system_hidden(params, tokens)[0, sample]), np.float32)
        scale = np.abs(ref_logits).max()
        per_position.extend(np.abs(mine - ref_logits).max(-1) / scale)
        for j, tok in enumerate(chosen[:steps + 1]):  # token j was read at n-1+j
            row = ref_logits[np.searchsorted(sample, n - 1 + j)]
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
        "forward_err_max": float(np.max(per_position)),
        "positions": len(per_position),
        "window_blocks_freed_in_the_steps": freed,
        "window_table_faults": table_faults,
        "cache_row_err_first_layer_max": cache_first,
        "cache_first_layer_rtol": ck["cache_first_layer_rtol"],
        "cache_row_err_median": cache_median, "cache_median_rtol": ck["cache_median_rtol"],
        "cache_row_err_decode_steps_max": cache_step,
        "cache_step_rtol": ck["cache_step_rtol"],
        "attend_rel_err": attend_err, "attend_rtol": ck["attend_rtol"],
        "edge_rel_err": edge_err, "edge_rtol": ck["edge_rtol"],
        "decode_edge_rel_err": step_edge_err, "decode_edge_rtol": ck["decode_edge_rtol"],
        "routing_compared": routed_rows, "routing_set_aside_share": under / routed_rows,
        "set_aside_max_share": ck["set_aside_max_share"],
        "route_margin_eps": ck["route_margin_eps"], "routing_mismatched": mismatched,
        "expert_part_rel_err": expert_err, "decode_rows": int(pool.slots),
        "expert_part_rel_err_decode_rows": step_err, "expert_rtol": ck["expert_rtol"]}
    ok = bool(within >= ck["served_min_share"]
              and line["forward_err_median"] <= ck["logit_median_rtol"]
              and line["forward_err_p90"] <= ck["logit_p90_rtol"]
              and freed > 0 and table_faults == 0
              and cache_first <= ck["cache_first_layer_rtol"]
              and cache_median <= ck["cache_median_rtol"]
              and cache_step <= ck["cache_step_rtol"]
              and attend_err <= ck["attend_rtol"]
              and edge_err <= ck["edge_rtol"]
              and step_edge_err <= ck["decode_edge_rtol"]
              and mismatched == 0
              and line["routing_set_aside_share"] <= ck["set_aside_max_share"]
              and max(expert_err, step_err) <= ck["expert_rtol"])
    ctx.emit({"line": "check", **line, "correct": ok})
    return ok
