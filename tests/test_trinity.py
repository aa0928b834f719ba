"""ISSUE 37: the trinity family (gated grouped-query attention, sliding-window
layers mixed with full ones, four norms a block, sigmoid-routed experts with
a shared one) against its plain float32 reference, at a small size on the
CPU, and served through the paged slot pool's two cache groups.

Weights are float32 here so that the tolerances say "the same mathematics in
another order": 2e-4 of the largest logit covers float32 accumulation in a
different order (an online softmax over blocks against a whole one, sorted
expert rows against dense masks). The bf16 case is held by the median and the
90th percentile over positions, as the chip's check holds it.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import trinity as ref
from deeplearning4j_tpu.models import transformer as tfm
from deeplearning4j_tpu.models import trinity as tr
from deeplearning4j_tpu.models.paged_decode import PagedDecodeSlotPool

TOL = 2e-4
WINDOW, BLOCK_T, MAX_LEN = 16, 8, 96


def small_cfg(**kw):
    """Five layers as the served cut has them: one dense, four with experts;
    sliding, sliding, sliding, full, sliding; a window of two blocks."""
    base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=5,
                num_dense_layers=1, num_attention_heads=6, num_key_value_heads=2,
                head_dim=16, intermediate_size=128, moe_intermediate_size=32,
                num_experts=16, expert_first=0, n_resident_experts=16,
                num_experts_per_tok=4, sliding_window=WINDOW,
                max_position_embeddings=MAX_LEN, param_dtype=jnp.float32,
                attn_impl="xla", moe_tile=8, prefill_chunk=16)
    base.update(kw)
    return tr.TrinityConfig(**base)


def model_of(cfg):
    """The reference's view of a config: the published key names."""
    return {k: getattr(cfg, k) for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
        "rms_norm_eps", "rope_theta", "sliding_window", "num_experts_per_tok",
        "route_scale", "expert_first", "n_resident_experts", "layer_types",
        "mup_enabled")}


def ref_logits(params, tokens, cfg, model=None):
    with jax.default_matmul_precision("highest"):
        model = model or model_of(cfg)
        return np.asarray(ref.logits(params, ref.hidden(params, tokens, model, 8), model))


def close(got, want, tol=TOL):
    err = np.max(np.abs(np.asarray(got, np.float32) - want)) / np.max(np.abs(want))
    assert err <= tol, err


@pytest.fixture(scope="module")
def small():
    # a chip's share: experts 4..7 of 16 are resident
    cfg = small_cfg(expert_first=4, n_resident_experts=4)
    return cfg, tr.init_params(jax.random.key(7), cfg)


# -- (a) the full forward ------------------------------------------------------


def test_layer_types_follow_the_published_rule():
    cfg = tr.TrinityConfig()
    assert cfg.layer_types.count(tr.FULL) == 15 and cfg.layer_types.count(tr.SLIDING) == 45
    assert [l for l, k in enumerate(cfg.layer_types) if k == tr.FULL][:3] == [3, 7, 11]
    cut = small_cfg()
    assert cut.layer_types == (tr.SLIDING,) * 3 + (tr.FULL, tr.SLIDING)
    assert [cut.window_of(l) for l in range(5)] == [WINDOW] * 3 + [None, WINDOW]
    assert [cut.rotates(l) for l in range(5)] == [True] * 3 + [False, True]


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_forward_matches_the_reference_past_the_window(small, impl):
    """40 positions against a window of 16: most queries of a sliding layer
    leave keys out; ``flash`` runs the windowed, grouped kernel (interpreted),
    rows in passes of ``prefill_chunk`` 16."""
    cfg, params = small
    cfg = dataclasses.replace(cfg, attn_impl=impl)
    tokens = np.random.RandomState(0).randint(1, 256, (2, 40)).astype(np.int32)
    close(tr.forward(params, tokens, cfg), ref_logits(params, tokens, cfg))


def test_forward_in_bf16_stays_near_the_reference():
    cfg = small_cfg(param_dtype=jnp.bfloat16, expert_first=4, n_resident_experts=4)
    params = tr.init_params(jax.random.key(3), cfg)
    tokens = np.random.RandomState(1).randint(1, 256, (1, 33)).astype(np.int32)
    got = np.asarray(tr.forward(params, tokens, cfg), np.float32)
    want = ref_logits(params, tokens, cfg)
    err = np.abs(got - want).max(-1)[0] / np.abs(want).max()
    # the statistics the chip's check holds: a bf16 hidden state carries an
    # expert across the router's boundary at a position or two (0.09-0.16 of
    # the largest logit there, seeds 3-5); the others lie near a hundredth
    assert np.median(err) <= 0.03 and np.quantile(err, 0.9) <= 0.06, err


@pytest.mark.parametrize("fact,perturbed", [
    ("gate", dict(attention_gate=False)),
    ("rotary_on_sliding_only", dict(rope_on_full_attention=True)),
    ("sandwich_norms", dict(sandwich_norm=False)),
    ("window", dict(sliding_window=WINDOW + 1)),
    ("embedding_scale", dict(mup_enabled=False)),
])
def test_a_program_without_one_fact_of_the_equations_fails(small, fact, perturbed):
    """Each fact of the block is held by the reference on its own: the program
    run without it is far from the reference where the sound one is at it."""
    cfg, params = small
    tokens = np.random.RandomState(2).randint(1, 256, (1, 40)).astype(np.int32)
    want = ref_logits(params, tokens, cfg)
    got = np.asarray(tr.forward(params, tokens, dataclasses.replace(cfg, **perturbed)))
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) > 50 * TOL


# -- (b) served: prefill, then decode through the two cache groups -------------


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


def test_family_answers_two_cache_groups(small):
    cfg, _ = small
    fam = cfg.decode_family()
    assert fam.cache_groups == ((1, None), (4, WINDOW))
    assert fam.arena_groups == (0, 0, 1, 1) and fam.cache_widths == (32,) * 4
    assert not fam.shares_prefix and not fam.speculative
    assert fam.place == {3: (0, 0), 0: (1, 0), 1: (1, 1), 2: (1, 2), 4: (1, 3)}


def test_one_decode_step_matches_the_full_forward_and_the_reference(small):
    """A prompt longer than the window: prefill stores the sliding group's
    visible rows only, and one step of the decode program (two tables, the
    grouped kernel with ``starts``) gives the logits the full forward gives."""
    cfg, params = small
    pool = PagedDecodeSlotPool(params, cfg, slots=3, block_T=BLOCK_T, max_len=MAX_LEN)
    prompt = np.random.RandomState(5).randint(1, 256, 37).astype(np.int32)
    slot, first = pool.admit(prompt, max_new_tokens=4)
    # rows behind the window went to the trash block: blocks 0 and 1 of 5
    assert list(pool._groups[1].tables[slot, :6] > 0) == [False, False, True, True, True, False]
    assert (pool._groups[0].tables[slot, :6] > 0).all()
    seq = np.concatenate([prompt, [first]]).astype(np.int32)
    tokens = np.zeros((3, 1), np.int32)
    positions = np.zeros((3, 1), np.int32)
    tokens[slot, 0], positions[slot, 0] = first, len(prompt)
    got, _, stats = pool.family.decode_window(
        params, jnp.asarray(tokens), jnp.asarray(positions), pool._arenas,
        tuple(jnp.asarray(g.tables) for g in pool._groups))
    close(got[slot, 0], np.asarray(tr.forward(params, seq[None], cfg)[0, -1]))
    close(got[slot, 0], ref_logits(params, seq[None], cfg)[0, -1])
    # one live token through four expert layers
    assert int(stats[0]) == 4 and 0 <= int(stats[1]) <= 16


def test_pool_serves_past_the_window_and_across_freed_blocks(small):
    """Ragged prompts shorter and longer than the window, decoded far enough
    that every slot hands blocks of the sliding group back while it is
    compared: every served token is the reference's greedy token, one decode
    program, and the sliding group never maps more than window / block_T + 2
    blocks a slot."""
    cfg, params = small
    rs = np.random.RandomState(11)
    prompts = [rs.randint(1, 256, n).astype(np.int32) for n in (7, 21, 40, 3)]
    pool = PagedDecodeSlotPool(params, cfg, slots=3, block_T=BLOCK_T, max_len=MAX_LEN)
    served, cap = {}, WINDOW // BLOCK_T + 2
    for prompt in prompts[:3]:
        slot, first = pool.admit(prompt, 30)
        served[slot] = (prompt, [first])
    for _ in range(29):
        for s, toks in pool.step().items():
            served[s][1].extend(toks)
        assert ((pool._groups[1].tables > 0).sum(axis=1) <= cap).all()
    stats = pool.block_stats()
    assert stats["kv_window_blocks_freed"] > 0 and pool.decode_traces == 1
    assert 0 < stats["swa_rows_read"] < stats["swa_rows_windowless"]
    for prompt, toks in served.values():
        assert len(toks) == 30
        assert_greedy(params, cfg, prompt, toks)
    # retire them, admit into the first one's place, go on
    for s in served:
        pool.release(s)
    d, first = pool.admit(prompts[3], 5)
    assert d == min(served)
    late = [first]
    for _ in range(4):
        late.extend(pool.step()[d])
    assert_greedy(params, cfg, prompts[3], late)
    assert pool.decode_traces == 1


def test_generate_through_the_pool_matches_the_reference(small):
    cfg, params = small
    rs = np.random.RandomState(4)
    prompts = [rs.randint(1, 256, n).astype(np.int32) for n in (19, 5, 33, 12)]
    pool = PagedDecodeSlotPool(params, cfg, slots=2, block_T=BLOCK_T, max_len=MAX_LEN)
    out = tfm.generate(params, prompts, 6, cfg, pool=pool)
    for prompt, toks in zip(prompts, out):
        assert_greedy(params, cfg, prompt, toks)
    assert pool.decode_traces == 1 and pool.free_slots == 2
    b = pool.block_stats()
    assert b["blocks_free"] == b["blocks_total"]


def test_cached_rows_of_both_groups_match_the_reference(small):
    """What the arenas hold after prefill and steps: the full group every
    row, the sliding group the rows a later query still sees (a position
    whose block was handed back reads the trash block)."""
    cfg, params = small
    pool = PagedDecodeSlotPool(params, cfg, slots=2, block_T=BLOCK_T, max_len=MAX_LEN)
    prompt = np.random.RandomState(8).randint(1, 256, 29).astype(np.int32)
    slot, first = pool.admit(prompt, 12)
    toks = [first]
    for _ in range(10):
        toks.extend(pool.step()[slot])
    n = len(prompt) + 10
    kg, vg, ks, vs = (np.asarray(x) for x in pool.cached_rows(slot, n))
    seq = np.concatenate([prompt, toks[:10]]).astype(np.int32)[None]
    model = model_of(cfg)
    with jax.default_matmul_precision("highest"):
        h = ref.embed(params, seq, model)
        want = []
        for l, p in enumerate(params["layers"]):
            parts = ref.block_parts(p, h, model, cfg.layer_types[l], 8)
            want.append((np.asarray(parts["attention"]["k"]).reshape(n, -1),
                         np.asarray(parts["attention"]["v"]).reshape(n, -1)))
            h = parts["out"]
    close(kg[0], want[3][0]), close(vg[0], want[3][1])
    seen = np.arange(n) >= (n - 1) - WINDOW + 1   # what the last query sees
    for at, l in enumerate((0, 1, 2, 4)):
        close(ks[at][seen], want[l][0][seen]), close(vs[at][seen], want[l][1][seen])


# -- (c) the experts' eight shares ---------------------------------------------


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips each hold four of sixteen experts and all compute the
    shared expert: the routed parts add up to the uncut reference's routed
    sum, and the shared expert is counted once."""
    cfg = small_cfg()
    params = tr.init_params(jax.random.key(9), cfg)
    p = params["layers"][2]
    u = jax.random.normal(jax.random.key(1), (24, 64), jnp.float32)
    live = jnp.ones(24, bool)
    model = model_of(cfg)
    with jax.default_matmul_precision("highest"):
        f32 = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        idx, w, _ = ref.routing(f32, u, model)
        whole = ref.swiglu(f32["shared"], u) + ref.routed_part(p["experts"], u, model, idx, w)
    total = np.zeros((24, 64), np.float32)
    for first in range(0, 16, 4):
        share = dataclasses.replace(cfg, expert_first=first, n_resident_experts=4)
        held = {**p, "experts": {k: x[first:first + 4] for k, x in p["experts"].items()}}
        out, stats = tr.ffn(share, held, u, live)
        shared = np.asarray(tr._swiglu(p["shared"], u))
        total += np.asarray(out) - shared
        assert int(stats[0]) == 24
    close(total + shared, np.asarray(whole))
