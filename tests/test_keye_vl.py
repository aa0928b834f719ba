"""ISSUE 35: the keye_vl family (grouped-query attention over a learned top-k
selection, multimodal rotary positions, softmax-routed experts all resident)
against its plain float32 reference, at a small size on the CPU, and served
through the paged slot pool with its three arenas.

Weights are float32 here so that the tolerances say "the same mathematics in
another order": 2e-4 of the largest logit covers float32 accumulation in a
different order (sorted expert rows against dense masks, a gathered selection
against a masked dense softmax). A selection that flipped would move a logit
by a tenth. The bf16 case allows 5e-2: eight bits of mantissa through two
layers, at a size where no score lies within bf16 of the selection's edge.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import keye_vl as ref
from deeplearning4j_tpu.models import keye_vl as kv
from deeplearning4j_tpu.models import kimi_k2 as k2
from deeplearning4j_tpu.models import transformer as tfm
from deeplearning4j_tpu.models.paged_decode import PagedDecodeSlotPool

TOL = 2e-4


def small_cfg(**kw):
    base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                num_attention_heads=8, num_key_value_heads=2, head_dim=16,
                moe_intermediate_size=32, num_experts=16, n_resident_experts=16,
                num_experts_per_tok=4, mrope_section=(2, 3, 3), index_n_heads=4,
                index_head_dim=8, index_topk=8, index_q_chunk=16,
                max_position_embeddings=64, param_dtype=jnp.float32, moe_tile=8,
                moe_chunk=32)
    base.update(kw)
    return kv.KeyeVLConfig(**base)


def model_of(cfg):
    """The reference's view of a config: the published key names."""
    return {"num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_key_value_heads,
            "head_dim": cfg.head_dim, "rms_norm_eps": cfg.rms_norm_eps,
            "rope_theta": cfg.rope_theta,
            "rope_scaling": {"mrope_section": list(cfg.mrope_section)},
            "sa_config": {"indexer_head_dim": cfg.index_head_dim,
                          "indexer_num_heads": cfg.index_n_heads,
                          "topk": cfg.index_topk},
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "expert_first": cfg.expert_first}


def ref_logits(params, tokens, cfg, pos3=None):
    with jax.default_matmul_precision("highest"):
        model = model_of(cfg)
        return np.asarray(ref.logits(
            params, ref.hidden(params, tokens, model, pos3), model))


def close(got, want, tol=TOL):
    err = np.max(np.abs(np.asarray(got, np.float32) - want)) / np.max(np.abs(want))
    assert err <= tol, err


@pytest.fixture(scope="module")
def small():
    cfg = small_cfg()
    return cfg, kv.init_params(jax.random.key(7), cfg)


def tokens_of(seed, *shape):
    return np.random.RandomState(seed).randint(1, 256, shape).astype(np.int32)


# -- (a) the full forward ------------------------------------------------------


@pytest.mark.parametrize("T,topk", [
    (40, 8),     # the selection bites: 8 of up to 40 rows, three query chunks
    (40, 64),    # a context <= topk: every visible row is selected
    (21, 8),     # a ragged last chunk of queries and of expert rows
], ids=["topk8_of_40", "topk64_of_40_is_full_attention", "topk8_of_21"])
def test_forward_matches_the_reference(small, T, topk):
    cfg, params = small
    cfg = dataclasses.replace(cfg, index_topk=topk)
    tokens = tokens_of(0, 2, T)
    close(kv.forward(params, tokens, cfg), ref_logits(params, tokens, cfg))


def test_a_context_within_topk_is_full_causal_attention(small):
    """With ``topk`` >= the context the indexer decides nothing: the layer is
    grouped-query causal attention, whatever the index weights are."""
    cfg, params = small
    cfg = dataclasses.replace(cfg, index_topk=64)
    tokens = tokens_of(1, 1, 33)
    scrambled = {**params, "layers": [
        {**p, "wqi": -p["wqi"], "wwi": p["wwi"][::-1]} for p in params["layers"]]}
    assert np.array_equal(np.asarray(kv.forward(params, tokens, cfg)),
                          np.asarray(kv.forward(scrambled, tokens, cfg)))
    # and with the selection on, the index weights DO decide
    biting = dataclasses.replace(cfg, index_topk=8)
    assert not np.allclose(np.asarray(kv.forward(params, tokens, biting)),
                           np.asarray(kv.forward(scrambled, tokens, biting)), atol=1e-3)


def test_forward_in_bf16_stays_near_the_reference():
    cfg = small_cfg(param_dtype=jnp.bfloat16, index_topk=64)
    params = kv.init_params(jax.random.key(3), cfg)
    tokens = tokens_of(1, 1, 17)
    close(kv.forward(params, tokens, cfg), ref_logits(params, tokens, cfg), 5e-2)


# -- the selection -------------------------------------------------------------


def brute_selection(scores, k):
    """numpy: the k largest of a row, ties to the lower index, -inf never."""
    out = np.zeros(scores.shape, bool)
    for r, row in enumerate(scores):
        order = sorted(range(len(row)), key=lambda s: (-row[s], s))
        for s in order[:k]:
            out[r, s] = row[s] > -np.inf
    return out


@pytest.mark.parametrize("which", ["program", "reference"])
def test_ties_go_to_the_lower_row_and_unseen_rows_are_never_selected(which):
    inf = np.inf
    scores = np.array([
        [1.0, 3.0, 3.0, 3.0, 0.5, 3.0, -inf, -inf],   # four tie AT the edge: rows 1, 2, 3
        [2.0, 2.0, 2.0, 2.0, 2.0, 2.0, 2.0, 2.0],     # all equal: the first three
        [5.0, -inf, -inf, -inf, -inf, -inf, -inf, -inf],  # one visible row, k = 3
        [0.0, -1.0, 4.0, 0.0, 0.0, 7.0, 0.0, -inf],   # zeros tie below two winners
    ], np.float32)
    select = (kv.selected if which == "program" else ref.select)
    got = np.asarray(select(jnp.asarray(scores), 3))
    assert np.array_equal(got, brute_selection(scores, 3))
    assert got[0].tolist() == [False, True, True, True, False, False, False, False]
    assert got[1].tolist() == [True] * 3 + [False] * 5
    assert got[2].sum() == 1 and got[3].tolist() == [
        True, False, True, False, False, True, False, False]
    # topk >= the row: everything visible
    assert np.array_equal(np.asarray(select(jnp.asarray(scores), 8)), scores > -inf)


def test_decode_selection_is_the_prefill_selection(small):
    """The decode step's top-k over a slot's cached index keys picks the rows
    the prefill's mask picks for the same query."""
    cfg, params = small
    T = 37
    tokens = tokens_of(2, 1, T)
    p = params["layers"][0]
    x = k2._rms(params["embed"][tokens].astype(jnp.float32), p["attn_norm"],
                cfg.rms_norm_eps)
    pos3 = kv.text_positions(jnp.arange(T)[None])
    _, _, _, qi, ki, wi = kv.attention_rows(cfg, p, x, pos3)
    mask = np.asarray(kv.selection_mask(cfg, qi, ki, wi))[0]
    assert mask.sum(-1).tolist() == [min(t + 1, 8) for t in range(T)]
    assert not np.triu(mask, 1).any()
    scores = np.asarray(kv.index_scores(qi[:, -1:], ki, wi[:, -1:]))[0, 0]
    assert set(np.argsort(-scores, kind="stable")[:8]) == set(np.flatnonzero(mask[-1]))


def sorted_select(scores, cell_of_row, limits, k):
    """What a decode step ran before ``decode_select``: one stable sort of
    the masked scores, the largest first, the rows' cells riding along."""
    R = scores.shape[1]
    scores = jnp.where(jnp.arange(R)[None, :] < limits[:, None], scores, -jnp.inf)
    falling, cells = jax.lax.sort((-scores, cell_of_row), num_keys=1, is_stable=True)
    chosen = jnp.pad(falling[:, :k] < jnp.inf, ((0, 0), (0, max(0, k - R))))
    return chosen, jnp.pad(cells[:, :k], ((0, 0), (0, max(0, k - R))))


def as_mask(chosen, cells, n):
    """A selection as the set of cells it names, whatever their order."""
    chosen, cells = np.asarray(chosen), np.asarray(cells)
    mask = np.zeros((chosen.shape[0], n), bool)
    for s in range(chosen.shape[0]):
        mask[s, cells[s][chosen[s]]] = True
        assert mask[s].sum() == chosen[s].sum()   # no cell named twice
    return mask


@pytest.mark.parametrize("S,R,k,tied,limits", [
    # integer scores: many more rows tie at the threshold than there is room;
    # a dead slot, limits = k, > k, the whole row, rows ending mid-chunk
    (5, 300, 40, True, [0, 40, 103, 267, 300]),
    (3, 1024, 200, True, [1024, 0, 777]),
    (6, 640, 128, False, [0, 127, 128, 129, 513, 640]),     # below, at, above k
    (2, 256, 256, False, [0, 200]),                         # k = R: no selection
    (3, 64, 100, False, [64, 0, 9]),                        # k > R
    (11, 200, 8, False, [0, 1, 7, 8, 9, 50, 199, 200, 3, 0, 129]),
], ids=["ties_past_the_room", "ties_long", "around_k", "k_is_R", "k_over_R", "eleven_slots"])
def test_decode_select_is_the_stable_sort_it_replaces(S, R, k, tied, limits):
    """The same SET of rows as the sort (the cells scattered into a mask, a
    row's count included), ``min(limit, k)`` places chosen from place 0 in
    row order, cell 0 in every other place."""
    from deeplearning4j_tpu.kernels.sparse_attention import decode_select

    rs = np.random.RandomState(S * R + k)
    scores = (rs.randint(0, 4, (S, R)) if tied else rs.randn(S, R)).astype(np.float32)
    cells = rs.permutation(2 ** 24)[:S * R].reshape(S, R).astype(np.int32)
    args = (jnp.asarray(scores), jnp.asarray(cells), jnp.asarray(limits, jnp.int32))
    chosen, got = map(np.asarray, decode_select(*args, k))
    assert chosen.shape == got.shape == (S, k)
    limits = np.asarray(limits)
    n = np.minimum(limits, k)
    assert np.array_equal(chosen, np.arange(k)[None, :] < n[:, None])
    assert not got[~chosen].any()
    want = brute_selection(np.where(np.arange(R)[None] < limits[:, None], scores, -np.inf), k)
    for s in range(S):   # in row order
        assert got[s, :n[s]].tolist() == cells[s][want[s]].tolist()
    # as sets, against the sort
    index = {int(c): i for i, c in enumerate(cells.reshape(-1))}
    def rows(chosen, cells):
        flat = np.vectorize(lambda c: index.get(int(c), 0))(np.asarray(cells))
        return as_mask(chosen, flat, S * R)
    assert np.array_equal(rows(chosen, got), rows(*sorted_select(*args, k)))


@pytest.fixture(scope="module")
def attending(small):
    """A pool with two live slots (29 and 5 rows cached) and a dead one, and
    random queries: what ``SparseGQADecodeFamily._attend`` takes."""
    cfg, params = small
    pool = PagedDecodeSlotPool(params, cfg, slots=3, block_T=8, max_len=64)
    limits = np.zeros(3, np.int32)
    for seed, n in ((5, 29), (6, 5)):
        slot, _ = pool.admit(tokens_of(seed, n), 4)
        limits[slot] = n
    tables, limits = jnp.asarray(pool._tables), jnp.asarray(limits)
    rs = np.random.RandomState(3)
    H, hd, HI, dI = (cfg.num_attention_heads, cfg.head_dim, cfg.index_n_heads,
                     cfg.index_head_dim)
    q, qi, wi = (jnp.asarray(rs.randn(3, *shape).astype(np.float32))
                 for shape in ((H, hd), (HI, dI), (HI,)))
    live = limits > 0
    args = (q, qi, wi, pool._arenas, 0, tables, limits)
    rest = (jnp.argsort(~live, stable=True).astype(jnp.int32),)
    return pool, args, kv._cell_of_row(tables, 8), rest


def test_attend_over_the_selected_set_is_the_sorted_paths(attending, monkeypatch):
    """The attention's output with the selection in row order matches the
    sort's order to float tolerance, over the same set of cells."""
    pool, args, cell_of_row, rest = attending
    o, chosen, cells = pool.family._attend(*args, cell_of_row, *rest)
    assert np.asarray(chosen).sum(-1).tolist() == np.minimum(
        np.asarray(args[-1]), 8).tolist()

    def by_sort(scores, cell_of_row, limits, k):
        return (*sorted_select(scores, cell_of_row, limits, k),
                jnp.zeros(scores.shape[0], bool))

    monkeypatch.setattr(kv, "decode_select_counted", by_sort)
    o_sorted, chosen_sorted, cells_sorted = pool.family._attend(*args, cell_of_row, *rest)
    n_cells = pool.n_blocks * 8
    assert np.array_equal(as_mask(chosen, cells, n_cells),
                          as_mask(chosen_sorted, cells_sorted, n_cells))
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_sorted), rtol=1e-5, atol=1e-6)


def test_attend_reads_the_cells_it_is_given(attending):
    """``cells`` come from the ``cell_of_row`` operand, never from the
    tables: every cell given one row later comes back one later (the check's
    ``decode_wrong_rows`` control relies on it)."""
    pool, args, cell_of_row, rest = attending
    _, chosen, cells = pool.family._attend(*args, cell_of_row, *rest)
    _, chosen_off, cells_off = pool.family._attend(*args, cell_of_row + 1, *rest)
    assert np.array_equal(np.asarray(chosen), np.asarray(chosen_off))
    assert np.array_equal(np.asarray(cells_off)[np.asarray(chosen)],
                          np.asarray(cells)[np.asarray(chosen)] + 1)


# -- multimodal rotary ---------------------------------------------------------


def test_mrope_with_three_distinct_channels_matches_the_reference(small):
    cfg, params = small
    T = 24
    tokens = tokens_of(4, 2, T)
    rs = np.random.RandomState(5)
    # an image span: time stands still while height and width walk a grid
    pos3 = np.stack([np.sort(rs.randint(0, 30, (2, T)), -1),
                     rs.randint(0, 9, (2, T)), rs.randint(0, 13, (2, T))], -1)
    got = kv.forward(params, tokens, cfg, positions=jnp.asarray(pos3))
    want = ref_logits(params, tokens, cfg, jnp.asarray(pos3))
    close(got, want)
    # the channels matter: text positions give other logits
    assert np.abs(np.asarray(got) - ref_logits(params, tokens, cfg)).max() > 1e-3


def test_mrope_with_equal_channels_is_plain_rotary(small):
    cfg, _ = small
    x = jax.random.normal(jax.random.key(1), (3, 5, cfg.head_dim))
    pos = jnp.asarray([0, 1, 7])
    got = kv.mrope(x, kv.text_positions(pos), cfg)
    half = cfg.head_dim // 2
    ang = np.asarray(pos, np.float32)[:, None] * cfg.rope_theta ** (
        -np.arange(half, dtype=np.float32) / half)
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    x1, x2 = np.asarray(x[..., :half]), np.asarray(x[..., half:])
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)
    # a pair turns by its own section's channel: (2, 3, 3) pairs of 8
    only_h = kv.mrope(x, jnp.asarray([[0, 1, 0]] * 3), cfg)
    moved = np.abs(np.asarray(only_h) - np.asarray(x)).max((0, 1)) > 1e-6
    assert moved.tolist() == ([False] * 2 + [True] * 3 + [False] * 3) * 2


def test_the_index_rotation_turns_the_leading_half_of_the_index_lanes(small):
    cfg, _ = small
    x = jnp.ones((1, cfg.index_head_dim))
    moved = np.abs(np.asarray(kv.index_rope(x, jnp.asarray([3]), cfg)) - 1.0) > 1e-6
    assert moved[0].tolist() == [True] * 4 + [False] * 4


# -- served: prefill, then decode through the three arenas ----------------------


def reference_rows(params, cfg, prompt, served):
    """The reference's logits at the positions where ``served`` was read: one
    full forward over the prompt and the served tokens."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    return ref_logits(params, seq[None], cfg)[0, len(prompt) - 1:]


def assert_greedy(params, cfg, prompt, served):
    rows = reference_rows(params, cfg, prompt, np.asarray(served))
    assert len(rows) == len(served)
    for tok, row in zip(served, rows):
        assert (row.max() - row[tok]) / np.abs(row).max() <= TOL
    assert list(served) == [int(np.argmax(row)) for row in rows]


def test_one_decode_step_matches_the_full_forward_and_the_reference(small):
    cfg, params = small
    pool = PagedDecodeSlotPool(params, cfg, slots=3, block_T=8, max_len=64)
    prompt = tokens_of(5, 29)
    slot, first = pool.admit(prompt, max_new_tokens=4)
    seq = np.concatenate([prompt, [first]]).astype(np.int32)
    tokens = np.zeros((3, 1), np.int32)
    positions = np.zeros((3, 1), np.int32)
    tokens[slot, 0], positions[slot, 0] = first, len(prompt)
    got, _, stats = pool.family.decode_window(
        params, jnp.asarray(tokens), jnp.asarray(positions), pool._arenas,
        jnp.asarray(pool._tables))
    close(got[slot, 0], np.asarray(kv.forward(params, seq[None], cfg)[0, -1]))
    close(got[slot, 0], ref_logits(params, seq[None], cfg)[0, -1])
    # one live token through two layers: 30 rows scored, 8 read, a layer
    # ... its selection decided by the threshold in both, with no tie to break
    assert stats.tolist()[:1] == [2] and stats.tolist()[4:] == [2 * 30, 2 * 8, 2, 0]


def test_pool_serves_ragged_prompts_token_for_token_with_one_decode_program(small):
    """Ragged lengths across block and chunk boundaries, shorter and longer
    than the selection, a dead slot beside live ones, admit / retire churn:
    every served token is the reference's greedy token and the decode program
    is traced once."""
    cfg, params = small
    rs = np.random.RandomState(11)
    prompts = [rs.randint(1, 256, n).astype(np.int32) for n in (5, 19, 33, 12)]
    pool = PagedDecodeSlotPool(params, cfg, slots=3, block_T=8, max_len=64)
    out = tfm.generate(params, prompts, 6, cfg, pool=pool)
    assert pool.decode_traces == 1
    for prompt, toks in zip(prompts, out):
        assert len(toks) == 6
        assert_greedy(params, cfg, prompt, toks)
    served = {}
    for prompt in prompts[:3]:
        slot, first = pool.admit(prompt, 6)
        served[slot] = [first]
    a, b, c = served
    pool.release(b)
    for _ in range(2):
        for s, toks in pool.step().items():
            served[s].extend(toks)
    assert len(served[b]) == 1  # nothing of a released slot is stepped
    d, first = pool.admit(prompts[3], 6)
    assert d == b
    served[d] = [first]
    for _ in range(2):
        for s, toks in pool.step().items():
            served[s].extend(toks)
    assert pool.decode_traces == 1
    assert [len(served[s]) for s in (a, c, d)] == [5, 5, 3]
    for slot, prompt in ((a, prompts[0]), (c, prompts[2]), (d, prompts[3])):
        assert_greedy(params, cfg, prompt, served[slot])


def test_the_cache_holds_the_references_rows_in_three_arenas(small):
    """K (rotated), V and the index key of every position, prefilled or
    written by a decode step, are the reference's; a token stores them in
    three arenas."""
    cfg, params = small
    pool = PagedDecodeSlotPool(params, cfg, slots=2, block_T=8, max_len=64)
    prompt = tokens_of(8, 21)
    slot, first = pool.admit(prompt, 5)
    served = [first]
    for _ in range(3):
        served.extend(pool.step()[slot])
    n = len(prompt) + 3
    k, v, ki = pool.cached_rows(slot, n)
    assert k.shape == v.shape == (2, n, 2 * 16) and ki.shape == (2, n, 128)
    seq = np.concatenate([prompt, served[:3]]).astype(np.int32)[None]
    model = model_of(cfg)
    with jax.default_matmul_precision("highest"):
        h = ref.embed(params, seq)
        for l, p in enumerate(params["layers"]):
            parts = ref.block_parts(p, h, ref.text_positions(1, n), model)
            att = parts["attention"]
            for got, want in ((k[l], att["k"][0]), (v[l], att["v"][0]),
                              (ki[l][:, :8], att["ki"][0])):
                np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                           rtol=2e-4, atol=2e-5)
            assert not np.asarray(ki[l][:, 8:]).any()  # the index arena's spare lanes
            h = parts["out"]
    with pytest.raises(ValueError, match="not active"):
        pool.cached_rows(1 - slot, 4)


def test_block_stats_count_rows_routing_and_the_bytes_a_token_stores(small, monkeypatch):
    from deeplearning4j_tpu.models import paged_decode

    cfg, params = small
    pool = PagedDecodeSlotPool(params, cfg, slots=2, block_T=8, max_len=64)
    opened, real_span = [], paged_decode.span

    def recording_span(name, **stats):
        opened.append((name, stats))
        return real_span(name, **stats)

    monkeypatch.setattr(paged_decode, "span", recording_span)
    pool.admit(tokens_of(1, 20), 4)     # longer than the selection of 8
    pool.admit(tokens_of(2, 3), 4)      # shorter
    for _ in range(3):
        pool.step()
    prefill = [stats for name, stats in opened if name == "kv.prefill"]
    assert [s["bucket"] for s in prefill] == [32, 16]
    dispatch = [stats for name, stats in opened if name == "kv.step.dispatch"]
    assert set(dispatch[0]) == {"live_blocks", "mapped_blocks"}
    assert set(dispatch[1]) == {"live_blocks", "mapped_blocks", *kv.STEP_STATS}
    # the step fetched last: rows 21 + 4 scored, 8 + 4 read, in each of 2 layers
    assert dispatch[1]["live_rows"] == 2 * 25 and dispatch[1]["selected_rows"] == 2 * 12
    b = pool.block_stats()
    # layers x (K 32 + V 32 + an index key's 128 lanes) x float32
    assert b["kv_cache_bytes_per_token"] == 2 * (32 + 32 + 128) * 4
    assert b["dsa_live_rows"] == 2 * (25 + 27 + 29)
    assert b["dsa_selected_rows"] == 2 * (12 + 13 + 14)
    # the long slot's selection is a threshold's in every layer of every step
    assert b["dsa_thresholded"] == 3 * 2 and b["dsa_tie_breaks"] == 0
    assert b["moe_routed_tokens"] == 3 * 2 * 2                 # steps x live x layers
    assert b["moe_experts_resident"] == 3 * 2 * 16
    assert 0 < b["moe_experts_touched"] <= b["moe_resident_assignments"]
    assert b["moe_resident_assignments"] == b["moe_load_sum"] == 4 * b["moe_routed_tokens"]
    assert set(pool.last_step_stats) == set(kv.STEP_STATS)


def test_block_stats_count_the_tie_breaks_of_a_selection_of_equal_scores(small):
    """Index head weights of zero score every cached row 0: a context longer
    than the selection ties all of its rows at the threshold, and the first
    8 enter."""
    cfg, params = small
    flat = {**params, "layers": [{**p, "wwi": jnp.zeros_like(p["wwi"])}
                                 for p in params["layers"]]}
    pool = PagedDecodeSlotPool(flat, cfg, slots=3, block_T=8, max_len=64)
    pool.admit(tokens_of(1, 20), 4)     # 21, 22 rows: tied past the room
    pool.admit(tokens_of(2, 8), 4)      # 9, 10 rows: tied past the room too
    pool.admit(tokens_of(3, 3), 4)      # within the selection: no threshold
    for _ in range(2):
        pool.step()
    b = pool.block_stats()
    assert b["dsa_thresholded"] == b["dsa_tie_breaks"] == 2 * 2 * 2   # steps x slots x layers
    assert b["dsa_selected_rows"] == 2 * ((8 + 8 + 4) + (8 + 8 + 5))   # layers x steps


def test_pool_refuses_speculation_for_this_family_by_name(small):
    cfg, params = small
    with pytest.raises(ValueError, match="keye_vl"):
        PagedDecodeSlotPool(params, cfg, slots=2, block_T=8, max_len=64,
                            draft_params=params, draft_cfg=cfg)


def test_config_refuses_sections_that_do_not_cover_a_head():
    with pytest.raises(ValueError, match="mrope_section"):
        small_cfg(mrope_section=(2, 3, 4))
    with pytest.raises(ValueError, match="evenly"):
        small_cfg(num_attention_heads=6, num_key_value_heads=4)


# -- the shared expert layer's new scoring ------------------------------------


def test_four_shares_of_the_softmax_routers_experts_add_up_to_the_uncut_layer(small):
    """The guide's tie test, on ``kimi_k2.route`` / ``resident_experts`` under
    softmax scores and normalised top-k weights: four chips that each hold a
    quarter of the 16 experts compute parts that add up to what the uncut
    reference gives for the whole layer (there is no shared expert to count
    once)."""
    cfg, params = small
    p = params["layers"][0]
    u = jax.random.normal(jax.random.key(2), (19, cfg.hidden_size), jnp.float32)
    live = jnp.ones(19, bool)
    total = 0.0
    for share in range(4):
        part_cfg = dataclasses.replace(cfg, expert_first=4 * share, n_resident_experts=4)
        part_p = {**p, "experts": {n: x[4 * share:4 * share + 4]
                                   for n, x in p["experts"].items()}}
        out, stats = kv.ffn(part_cfg, part_p, u, live)
        assert int(stats[0]) == 19 and 0 < int(stats[1]) < 19 * 4
        total = total + out
    model = model_of(cfg)
    with jax.default_matmul_precision("highest"):
        idx, w, r = ref.routing(p, u, model)
        want = ref.routed_part(p, u, model, idx, w)
    close(total, np.asarray(want))
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(r).sum(-1), 1.0, rtol=1e-6)
    # the program's routing is the reference's: softmax, top 4, normalised
    got_idx, got_w = k2.route(cfg, p, u)
    assert np.array_equal(np.sort(np.asarray(got_idx), -1), np.sort(np.asarray(idx), -1))
    np.testing.assert_allclose(np.sort(np.asarray(got_w), -1), np.sort(np.asarray(w), -1),
                               rtol=1e-5)


def test_stacked_experts_compute_what_a_list_of_experts_computes(small):
    """``resident_experts`` runs a loop an expert over a LIST of experts and
    one loop over all trips where they are STACKED: the same rows, the same
    sums."""
    cfg, params = small
    p = params["layers"][1]
    u = jax.random.normal(jax.random.key(9), (23, cfg.hidden_size), jnp.float32)
    live = jnp.arange(23) % 5 != 0
    idx, w = k2.route(cfg, p, u)
    as_list = {**p, "experts": [{n: x[e] for n, x in p["experts"].items()}
                                for e in range(cfg.n_resident_experts)]}
    stacked, s1 = k2.resident_experts(cfg, p, u, idx, w, live)
    listed, s2 = k2.resident_experts(cfg, as_list, u, idx, w, live)
    np.testing.assert_allclose(np.asarray(stacked), np.asarray(listed), rtol=1e-5, atol=1e-6)
    assert s1.tolist() == s2.tolist()
    assert not np.asarray(stacked)[~np.asarray(live)].any()
