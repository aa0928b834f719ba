"""The ``keye_vl`` family: a configuration file (the published key names at
its top level, the chip's share under ``model``) to the program's own
``KeyeVLConfig``, to weights made on the device, and the comparison with the
reference that decides ``correct`` for a served cell."""

from __future__ import annotations

import dataclasses
import functools
import os

import numpy as np

from benchmark import loadgen
from benchmark.reference import keye_vl as reference

#: faults a control run serves (``BENCHMARK_CHECK_CONTROL``): four in the
#: program's behaviour, made here of its configuration and its family (the
#: last three in the DECODE step alone: prefill stays sound), two in the
#: weights (``control_params``)
BEHAVIOUR_CONTROLS = ("no_selection", "stale_index_keys", "decode_no_selection",
                      "decode_wrong_rows")
CONTROLS = BEHAVIOUR_CONTROLS + ("drop_expert", "fp8_experts")


def _faulty_decode(cfg, control: str):
    """``cfg`` whose decode step alone is at fault; its prefill is sound.

    ``stale_index_keys``: the step hands back the index-key arena it was
    given, so its own index key is used by that step and then lost.
    ``decode_no_selection``: the step's select-and-attend keeps every live
    row (as long a selection as the cache). ``decode_wrong_rows``: the step
    selects the right rows and says so, but its attention reads the row
    AFTER each of them (the sort's payload is off by a row)."""
    from deeplearning4j_tpu.models.keye_vl import SparseGQADecodeFamily

    class Faulty(SparseGQADecodeFamily):
        if control == "stale_index_keys":
            def decode_window(self, params, tokens, positions, arenas, tables):
                logits, (k, v, _), stats = super().decode_window(
                    params, tokens, positions, arenas, tables)
                return logits, (k, v, arenas[2]), stats
        elif control == "decode_no_selection":
            def _attend(self, *args):
                return SparseGQADecodeFamily(dataclasses.replace(
                    self.cfg, index_topk=self.cfg.max_position_embeddings))._attend(*args)
        else:
            def _attend(self, q, qi, wi, arenas, layer, tables, limits, cell_of_row,
                        live_first):
                o, chosen, cells = super()._attend(
                    q, qi, wi, arenas, layer, tables, limits, cell_of_row + 1, live_first)
                return o, chosen, cells - 1

    class FaultyConfig(type(cfg)):
        def decode_family(self):
            return Faulty(self)

    return FaultyConfig(**{f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)})


def build_config(config: dict, *, on_tpu: bool, max_len=None):
    """``models.keye_vl.KeyeVLConfig`` as the cell runs it. In a control run
    named ``no_selection`` the selection is as long as the cache (every cached
    row is attended to, in prefill and in a step); the other faults of
    behaviour are the decode step's alone (``_faulty_decode``): the check has
    to say so."""
    import jax.numpy as jnp

    from benchmark.runners.serve_family import CONTROL_ENV
    from deeplearning4j_tpu.models.keye_vl import KeyeVLConfig

    sa, share = config["sa_config"], config["model"]
    if not config["norm_topk_prob"]:
        raise ValueError("the expert layer divides the chosen weights by their sum: "
                         "norm_topk_prob false is not implemented")
    cfg = KeyeVLConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_experts=config["num_experts"], expert_first=share["expert_first"],
        n_resident_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        rms_norm_eps=config["rms_norm_eps"], rope_theta=float(config["rope_theta"]),
        mrope_section=tuple(config["rope_scaling"]["mrope_section"]),
        index_n_heads=sa["indexer_num_heads"], index_head_dim=sa["indexer_head_dim"],
        index_topk=sa["topk"],
        **({"index_q_chunk": share["q_chunk"]} if "q_chunk" in share else {}),
        max_position_embeddings=max_len or config["max_position_embeddings"],
        param_dtype=jnp.dtype(share["param_dtype"]))
    control = os.environ.get(CONTROL_ENV)
    if control == "no_selection":
        cfg = dataclasses.replace(cfg, index_topk=cfg.max_position_embeddings)
    elif control in BEHAVIOUR_CONTROLS:
        cfg = _faulty_decode(cfg, control)
    return cfg


def reference_model(config: dict) -> dict:
    """What the reference reads: the published keys, and the chip's share."""
    return {**{k: v for k, v in config.items()
               if k in ("rope_scaling", "sa_config") or not isinstance(v, (list, str, dict))},
            "expert_first": config["model"]["expert_first"]}


def make_init(cfg):
    """The function of the KEY that makes every weight: jit it once, so the
    seed reaches the device as data and one program serves every seed."""
    from deeplearning4j_tpu.models.keye_vl import init_params

    return lambda key: init_params(key, cfg)


def control_params(params, control: str):
    """The weights a CONTROL run serves. ``fp8_experts`` rounds the first
    layer's experts through float8_e4m3 (the nearest precision below the
    configuration's bfloat16); ``drop_expert`` zeroes its first expert's way
    out; every other leaf is shared. The faults of behaviour
    (``build_config``) serve the sound weights. The reference keeps the sound
    weights and the published selection, so the check has to come out NOT
    correct (``runners/serve_family.py`` stops after it)."""
    import jax.numpy as jnp

    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r} (have: {CONTROLS})")
    if control in BEHAVIOUR_CONTROLS:
        return params
    p = params["layers"][0]
    if control == "fp8_experts":
        experts = {k: w.astype(jnp.float8_e4m3fn).astype(w.dtype)
                   for k, w in p["experts"].items()}
    else:
        experts = {**p["experts"], "wd": p["experts"]["wd"].at[0].set(0)}
    return {**params, "layers": [{**p, "experts": experts}, *params["layers"][1:]]}


def shapes(cfg, *, slots: int, block_T: int) -> dict:
    """What the work functions (``benchmark/work_keye_vl.py``) count from."""
    return {"hidden": cfg.hidden_size, "heads": cfg.num_attention_heads,
            "kv_heads": cfg.num_key_value_heads, "head_dim": cfg.head_dim,
            "index_heads": cfg.index_n_heads, "index_dim": cfg.index_head_dim,
            "topk": cfg.index_topk, "expert_width": cfg.moe_intermediate_size,
            "router_width": cfg.num_experts,
            "resident_experts": cfg.n_resident_experts,
            "experts_per_token": cfg.num_experts_per_tok,
            "layers": cfg.num_hidden_layers, "vocab": cfg.vocab_size,
            "slots": slots, "block_T": block_T,
            "max_len": cfg.max_position_embeddings,
            "weight_bytes": int(np.dtype(cfg.param_dtype).itemsize)}


def _highest(fn):
    import jax

    def run(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)
    return jax.jit(run)


def edge_stats(I, theirs, mine, full, topk: int):
    """Two selections of the same queries, I / theirs / mine [.., Q, T],
    ``full`` [.., Q] the queries that count (real, and leaving rows out):
    how many rows they disagree on, and how far from the selection's edge
    the farthest of them lies IN THE REFERENCE'S OWN ORDER of the query's
    rows, as a share of ``topk``: a row the reference keeps and the
    program drops counts the rows from it down to the last one kept, one
    the program adds counts those from the first one left out up to it.
    Counted in rows, the margin excuses the same share of a selection
    whatever shape a seed's scores take (a distance in standard
    deviations excused 22-67 % of the 2048: PERF.md, PR 35)."""
    import jax.numpy as jnp

    seen = I > -jnp.inf
    differ = seen & (mine != theirs) & full[..., None]
    dropped, added = differ & theirs, differ & ~theirs

    def outscored_by(score):  # rows of the query above ``score`` [.., Q]
        return jnp.sum(seen & (I > score[..., None]), -1)

    far_dropped = topk - outscored_by(jnp.max(jnp.where(dropped, I, -jnp.inf), -1))
    far_added = outscored_by(jnp.min(jnp.where(added, I, jnp.inf), -1)) - topk + 1
    far = jnp.maximum(jnp.where(jnp.any(dropped, -1), far_dropped, 0),
                      jnp.where(jnp.any(added, -1), far_added, 0))
    return {"flip_distance_max": jnp.max(far) / topk, "flips": jnp.sum(differ)}


def make_compare(cfg, model: dict, chunk: int):
    """The jitted pieces of the layer-by-layer comparison, each one program
    whatever the layer: the reference's block taken apart, and the program's
    selection, attention and experts ON THE REFERENCE'S INPUT, reduced on the
    device to the few numbers the check reads."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import keye_vl as kv
    from deeplearning4j_tpu.models.kimi_k2 import route

    topk = model["sa_config"]["topk"]

    def positions(x):
        return reference.text_positions(*x.shape[:2])

    ref_block = _highest(lambda p, h: reference.block_parts(
        p, h, positions(h), model, chunk))

    @jax.jit
    def selection(p, ref, real):
        """The program's selection of every query on the reference's input
        ``x``, against the reference's (``edge_stats``, over real queries
        that leave rows out)."""
        x, att = ref["x"], ref["attention"]
        _, _, _, qi, ki, wi = kv.attention_rows(cfg, p, x, positions(x))
        mine = kv.selection_mask(cfg, qi, ki, wi)
        n = jnp.sum(att["I"] > -jnp.inf, -1)
        return edge_stats(att["I"], att["selected"], mine, real & (n > topk), topk)

    family = cfg.decode_family()

    @functools.partial(jax.jit, static_argnames=("block_T", "max_len"))
    def decode_step(p, ref_p, ref, at, live, *, block_T, max_len):
        """ONE layer of the decode step's own select-and-attend
        (``family._attend``, the function ``decode_window`` calls, at the
        pool's slots, ``block_T`` and ``max_len``) over arenas that hold
        the REFERENCE's rows of this layer: slot ``i`` is a query at position
        ``at[i]`` of the reference's sequence with rows ``0 .. at[i]``
        cached (``live[i]`` false: a dead slot, which maps nothing). The
        query's q, index queries and head weights are the program's, of the
        reference's input. Against the reference: the selection the step
        says it made (``edge_stats``; a query that leaves nothing out has to
        select exactly the rows it sees), and its attention's output
        against float32 attention under THAT selection."""
        x, att = ref["x"], ref["attention"]
        width = x.shape[1]
        n_blocks = -(-width // block_T)

        def arena(rows, lanes):
            """[1, 1 + n_blocks, block_T, lanes]: block j + 1 holds positions
            j * block_T .., block 0 is the pool's null block."""
            rows = jnp.pad(rows.astype(cfg.param_dtype),
                           ((0, (1 + n_blocks) * block_T - width), (0, lanes - rows.shape[-1])))
            return jnp.roll(rows, block_T, axis=0).reshape(1, 1 + n_blocks, block_T, lanes)

        arenas = tuple(arena(att[name][0], lanes) for name, lanes in zip(
            ("k", "v", "ki"), family.cache_widths))
        blocks = jnp.arange(max_len // block_T, dtype=jnp.int32)[None, :]
        tables = jnp.where(live[:, None] & (blocks * block_T <= at[:, None]), blocks + 1, 0)
        limits = jnp.where(live, at + 1, 0)
        pos3 = positions(x)[0, at]
        q, _, _, qi, _, wi = kv.attention_rows(cfg, p, x[0, at][:, None], pos3[:, None])
        o, chosen, cells = family._attend(
            q[:, 0], qi[:, 0], wi[:, 0], arenas, 0, tables, limits,
            kv._cell_of_row(tables, block_T),
            jnp.argsort(~live, stable=True).astype(jnp.int32))
        # a selected row's position, by the table built above: cell - block_T
        mine = jnp.zeros((at.shape[0], width), bool).at[
            jnp.arange(at.shape[0])[:, None], jnp.where(chosen, cells - block_T, width)].set(
                True, mode="drop")
        I, theirs = att["I"][0, at], att["selected"][0, at]
        stats = edge_stats(I, theirs, mine, live & (at + 1 > topk), topk)
        exact = (jnp.sum(chosen, -1) == jnp.minimum(at + 1, topk)) & (
            (at + 1 > topk) | jnp.all(mine == theirs, -1))
        with jax.default_matmul_precision("highest"):
            want = reference.attend_given(ref_p, x[0, at], pos3, att["k"][0], att["v"][0],
                                          mine | ~live[:, None], model)
        # a query's error over ITS largest value: a late query's output is
        # the mean of ``topk`` rows, a tenth the size of an early one's
        err = jnp.max(jnp.abs(o.astype(jnp.float32) - want), -1) / jnp.max(jnp.abs(want), -1)
        return {"flip_distance_max": stats["flip_distance_max"], "flips": stats["flips"],
                "wrong_queries": jnp.sum(live & ~exact),
                "attend_err": jnp.max(jnp.where(live, err, 0.0))}

    @jax.jit
    def attention(p, ref, real):
        """The program's attention UNDER THE REFERENCE'S SELECTION, on the
        reference's input, against the reference's: max error over real rows
        as a share of the largest value."""
        x, att = ref["x"], ref["attention"]
        q, k, v = (a.reshape(*x.shape[:2], -1)
                   for a in kv.attention_rows(cfg, p, x, positions(x))[:3])
        # every real row selects something; a padded row is given itself
        mask = att["selected"] | ~real[..., None] & jnp.eye(x.shape[1], dtype=bool)
        out = jnp.dot(kv.masked_attention(cfg, q, k, v, mask), p["wo"],
                      preferred_element_type=jnp.float32)
        top = jnp.max(jnp.abs(jnp.where(real[..., None], att["out"], 0.0)))
        return jnp.max(jnp.abs(jnp.where(real[..., None], out - att["out"], 0.0))) / top

    @jax.jit
    def experts(p, u, idx, w):
        """The program's routing of rows ``u`` [N, D], and its experts' part
        under the routing it is GIVEN."""
        part, _ = kv.ffn(cfg, p, u, jnp.ones(u.shape[0], bool), routing=(idx, w))
        return route(cfg, p, u)[0], part

    @jax.jit
    def rows_error(cached, want):
        """A cached row against the reference's, a row: max |difference| over
        the largest value any row has. cached [n, lanes >= width]."""
        want = want.astype(jnp.float32)
        got = cached[:, :want.shape[-1]].astype(jnp.float32)
        return jnp.max(jnp.abs(got - want), axis=-1) / jnp.max(jnp.abs(want))

    return ref_block, selection, attention, experts, rows_error, decode_step


def check_served_path(ctx, pool, cfg, params, rs, reference_params=None) -> bool:
    """Prefill then decode through the three paged arenas against the
    reference's full forward (``prompt_lens``, a few steps each, decoded
    TOGETHER with ``bystander_lens`` further live slots and the pool's dead
    ones), and every layer's selection, attention, routing and experts
    against the reference's ON THE REFERENCE'S INPUT. Logits decide, not
    tokens. ``params`` is what is served; the reference reads
    ``reference_params`` (the same, but for a control run) and the PUBLISHED
    selection, whatever ``cfg`` says.

    bf16 hidden states differ from float32 ones by about a hundredth, which
    carries an expert across the router's top-k boundary at a few positions
    in a hundred (PERF.md, PR 31) and a cached row across the selection's
    edge for nearly every query: of the thousands of rows a query scores,
    some always lie nearer to the 2048th score than bf16 resolves. So what
    runs END TO END is held by medians and shares, and each layer's own
    arithmetic on the reference's input:

    (a) served: of the tokens the pool chose, ``served_min_share`` lie within
        ``argmax_gap_rtol`` x max|logit| of the reference's largest logit;
    (b) forward: over a sample of positions, the MEDIAN and the 90th
        percentile of the program's full forward's error, max over the
        vocabulary, stay under ``logit_median_rtol`` / ``logit_p90_rtol``;
    (c) cache: what the arenas hold of the compared slots after the steps
        (K, V and index key) matches the reference's rows: in the FIRST
        layer, whose input is the embedding in both, every row, prefilled or
        written by a step, to ``cache_first_layer_rtol`` (bf16's rounding of
        a stored value; a step that writes no index key leaves zeros there);
        in every layer, where the hidden states have drifted, the median row
        of a prompt to ``cache_median_rtol`` and every row a decode step
        wrote to ``cache_step_rtol``;
    (d) selection, given the reference's input: the program's selected set
        and the reference's differ only in rows that lie within
        ``select_margin`` x 2048 rows of the selection's edge in the
        reference's own order of the query's rows, so of a query's 2048 at
        most that share is excused, whatever shape a seed's scores take
        (attending to every cached row, or index keys of lower precision,
        put rows far from the edge on the wrong side);
    (e) attention, given the reference's input AND selection: to
        ``attend_rtol`` x the largest value;
    (f) routing, given the reference's expert-layer input: the chosen sets
        are EQUAL wherever the boundary margin is at least
        ``route_margin_eps`` (``set_aside_max_share`` may lie under it);
    (g) experts, given the reference's input AND routing: to ``expert_rtol``
        x the largest value, over all rows at once (the prefill program's
        passes and tile) and in groups of ``pool.slots`` rows (a decode
        step's rows and its short tile);
    (h) the DECODE step's select-and-attend (``decode_step`` above: the
        function a step calls, at the pool's sizes, on arenas that hold the
        reference's rows), a slot a query: the positions the steps wrote and
        a spread of the prompt's, two slots dead. The selection it says it
        made against the reference's as in (d) (``select_margin``; exact for
        a query that leaves nothing out), and its attention's output against
        float32 attention under that selection to ``decode_attend_rtol`` x
        the QUERY's own largest value (a stricter measure than (e)'s, which
        divides by the largest value of any row: a late query's output is the
        mean of 2048 rows, a tenth the size of an early one's, and bf16
        rounds each value by up to 0.4 % of itself): a step that selects
        other rows, or reads other rows than it selected, fails here whatever
        its tokens look like."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.keye_vl import prefill_forward

    ck = ctx.traffic["check"]
    steps = int(ck["decode_steps"])
    reference_params = params if reference_params is None else reference_params
    model = reference_model(ctx.config)
    compared = len(ck["prompt_lens"])
    held = []  # (prompt, slot, tokens chosen): the compared prompts first
    for n in (*ck["prompt_lens"], *ck["bystander_lens"]):
        prompt = loadgen.prompt_tokens(rs, int(n), cfg.vocab_size)
        slot, first = pool.admit(prompt, steps + 1)
        held.append((prompt, slot, [int(first)]))
    for _ in range(steps):
        out = pool.step()
        for _, slot, chosen in held:
            chosen.extend(int(x) for x in out[slot])
    cached = [pool.cached_rows(slot, len(prompt) + steps)
              for prompt, slot, _ in held[:compared]]
    for _, slot, _ in held:
        pool.release(slot)

    chunk = int(ck["reference_q_chunk"])  # a tile size of the reference, not semantics
    width = max(ck["prompt_lens"]) + steps
    width = -(-width // chunk) * chunk if width > chunk else width
    ref_block, selection, attention, experts, rows_error, decode_step = make_compare(
        cfg, model, chunk)
    embed = _highest(reference.embed)
    logits_of = _highest(lambda params, h: reference.logits(params, h, model))
    system_hidden = jax.jit(lambda p, t: prefill_forward(p, t, cfg)[0])
    family = cfg.decode_family()

    def same(a, b):
        return (np.sort(np.asarray(a), -1) == np.sort(b, -1)).all(-1)

    gaps, per_position = [], []
    flip_distance, attend_err = 0.0, 0.0
    under = mismatched = routed_rows = flips = 0
    step_flip_distance, step_attend_err, step_flips, step_wrong = 0.0, 0.0, 0, 0
    # (h)'s slots: dead ones first, so that the order of the live matters
    n_dead = max(min(2, pool.slots - steps), 0)
    live = jnp.arange(pool.slots) >= n_dead
    expert_err = step_err = 0.0
    cache_first, cache_median, cache_step = 0.0, 0.0, 0.0
    for (prompt, _, chosen), rows in zip(held[:compared], cached):
        n = len(prompt)
        seq = np.zeros((1, width), np.int32)
        seq[0, :n] = prompt
        seq[0, n:n + steps] = chosen[:steps]  # teacher-forced with the pool's tokens
        tokens = jnp.asarray(seq)
        real = jnp.arange(width)[None, :] < n + steps
        flat_real = np.asarray(real).reshape(-1)
        at = np.flatnonzero(flat_real)
        at = at[:len(at) // pool.slots * pool.slots].reshape(-1, pool.slots)[:8]
        h = embed(reference_params, tokens)
        queries = np.concatenate([np.zeros(n_dead), np.linspace(
            0, n - 1, max(pool.slots - n_dead - steps, 0)), np.arange(n, n + steps)])
        queries = jnp.asarray(queries[:pool.slots], jnp.int32)
        for l, (p, ref_p) in enumerate(zip(params["layers"], reference_params["layers"])):
            ref = ref_block(ref_p, h)
            sel = selection(p, ref, real)
            flip_distance = max(flip_distance, float(sel["flip_distance_max"]))
            flips += int(sel["flips"])
            attend_err = max(attend_err, float(attention(p, ref, real)))
            step = decode_step(p, ref_p, ref, queries, live,
                               block_T=pool.block_T, max_len=pool.max_len)
            step_flip_distance = max(step_flip_distance, float(step["flip_distance_max"]))
            step_attend_err = max(step_attend_err, float(step["attend_err"]))
            step_flips += int(step["flips"])
            step_wrong += int(step["wrong_queries"])
            for arena, name in enumerate(("k", "v", "ki")):
                err = np.asarray(rows_error(rows[arena][l],
                                            ref["attention"][name][0, :n + steps]))
                cache_median = max(cache_median, float(np.median(err[:n])))
                cache_step = max(cache_step, float(err[n:].max()))
                if l == 0:
                    cache_first = max(cache_first, float(err.max()))
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
            for group in at:
                step_idx, step_part = experts(p, u[group], idx[group], w[group])
                mismatched += int((kept[group] & ~same(step_idx, idx[group])).sum())
                step_err = max(step_err, float(
                    np.abs(np.asarray(step_part) - routed[group]).max() / top))
            h = ref["out"]
            del ref, sel
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
        "cache_row_err_first_layer_max": cache_first,
        "cache_first_layer_rtol": ck["cache_first_layer_rtol"],
        "cache_row_err_median": cache_median, "cache_median_rtol": ck["cache_median_rtol"],
        "cache_row_err_decode_steps_max": cache_step,
        "cache_step_rtol": ck["cache_step_rtol"],
        "select_flips": flips, "select_flip_distance_max": flip_distance,
        "select_margin": ck["select_margin"],
        "attend_rel_err": attend_err, "attend_rtol": ck["attend_rtol"],
        "decode_queries": int(jnp.sum(live)) * compared * len(params["layers"]),
        "decode_select_flips": step_flips,
        "decode_select_flip_distance_max": step_flip_distance,
        "decode_select_wrong_queries": step_wrong,
        "decode_attend_rel_err": step_attend_err,
        "decode_attend_rtol": ck["decode_attend_rtol"],
        "routing_compared": routed_rows, "routing_set_aside_share": under / routed_rows,
        "set_aside_max_share": ck["set_aside_max_share"],
        "route_margin_eps": ck["route_margin_eps"], "routing_mismatched": mismatched,
        "expert_part_rel_err": expert_err, "decode_rows": int(pool.slots),
        "expert_part_rel_err_decode_rows": step_err, "expert_rtol": ck["expert_rtol"]}
    ok = bool(within >= ck["served_min_share"]
              and line["forward_err_median"] <= ck["logit_median_rtol"]
              and line["forward_err_p90"] <= ck["logit_p90_rtol"]
              and cache_first <= ck["cache_first_layer_rtol"]
              and cache_median <= ck["cache_median_rtol"]
              and cache_step <= ck["cache_step_rtol"]
              and flip_distance <= ck["select_margin"]
              and attend_err <= ck["attend_rtol"]
              and step_flip_distance <= ck["select_margin"]
              and step_wrong == 0
              and step_attend_err <= ck["decode_attend_rtol"]
              and mismatched == 0
              and line["routing_set_aside_share"] <= ck["set_aside_max_share"]
              and max(expert_err, step_err) <= ck["expert_rtol"])
    ctx.emit({"line": "check", **line, "correct": ok})
    return ok
