"""ISSUE 31: the kimi_k2 family (latent attention, routed experts with a
shared expert) against its plain float32 reference, at a small size on the
CPU, and served through the paged slot pool.

Weights are float32 here so that the tolerances say "the same mathematics in
another order": 2e-4 of the largest logit covers float32 accumulation in a
different order (absorbed against expanded attention, sorted expert rows
against dense masks, an online softmax against a whole one). The bf16 cases
allow 5e-2: eight bits of mantissa through three layers.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import kimi_k2 as ref
from deeplearning4j_tpu.models import kimi_k2 as k2
from deeplearning4j_tpu.models import transformer as tfm
from deeplearning4j_tpu.models.paged_decode import PagedDecodeSlotPool

TOL = 2e-4


def small_cfg(**kw):
    base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=3,
                first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=32,
                kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
                n_routed_experts=16, expert_first=0, n_resident_experts=16,
                num_experts_per_tok=4, max_position_embeddings=64,
                param_dtype=jnp.float32, attn_impl="xla", moe_tile=8)
    base.update(kw)
    return k2.KimiK2Config(**base)


def model_of(cfg):
    """The reference's view of a config: the published key names."""
    return {
        "num_attention_heads": cfg.num_attention_heads,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "kv_lora_rank": cfg.kv_lora_rank, "rms_norm_eps": cfg.rms_norm_eps,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": {"factor": cfg.rope_factor, "mscale": cfg.rope_mscale,
                         "mscale_all_dim": cfg.rope_mscale_all_dim,
                         "beta_fast": cfg.rope_beta_fast,
                         "beta_slow": cfg.rope_beta_slow,
                         "original_max_position_embeddings": cfg.rope_original_max},
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "expert_first": cfg.expert_first,
        "n_resident_experts": cfg.n_resident_experts,
    }


def ref_logits(params, tokens, cfg):
    with jax.default_matmul_precision("highest"):
        model = model_of(cfg)
        return np.asarray(ref.logits(params, ref.hidden(params, tokens, model), model))


def close(got, want, tol=TOL):
    err = np.max(np.abs(np.asarray(got, np.float32) - want)) / np.max(np.abs(want))
    assert err <= tol, err


@pytest.fixture(scope="module")
def small():
    # a chip's share: experts 4..7 of 16 are resident
    cfg = small_cfg(expert_first=4, n_resident_experts=4)
    return cfg, k2.init_params(jax.random.key(7), cfg)


# -- (a) the full forward ------------------------------------------------------


def test_forward_matches_the_reference(small):
    cfg, params = small
    tokens = np.random.RandomState(0).randint(1, 256, (2, 21)).astype(np.int32)
    close(k2.forward(params, tokens, cfg), ref_logits(params, tokens, cfg))


def test_forward_in_bf16_stays_near_the_reference():
    cfg = small_cfg(param_dtype=jnp.bfloat16, expert_first=4, n_resident_experts=4)
    params = k2.init_params(jax.random.key(3), cfg)
    tokens = np.random.RandomState(1).randint(1, 256, (1, 17)).astype(np.int32)
    close(k2.forward(params, tokens, cfg), ref_logits(params, tokens, cfg), 5e-2)


def test_yarn_frequencies_blend_between_the_two_regimes():
    cfg = k2.KimiK2Config()  # the published rotary settings
    inv = np.asarray(k2.yarn_inv_freq(cfg))
    extra = 50000.0 ** (-np.arange(0, 64, 2) / 64)
    # low, high = floor / ceil of 64 ln(4096 / (beta 2 pi)) / (2 ln 50000)
    assert np.allclose(inv[:8], extra[:8], rtol=1e-6)        # pairs below `low`
    assert np.allclose(inv[20:], extra[20:] / 64, rtol=1e-6)  # past `high`
    assert np.all(np.diff(inv) < 0)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * 1.4158883 ** 2, rel=1e-6)


# -- (b), (c) served: prefill, then decode through the latent cache ------------


def reference_rows(params, cfg, prompt, served):
    """The reference's logits at the positions where ``served`` was read:
    one full forward over the prompt and the served tokens (causal, so each
    row is what a recompute of that prefix gives)."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    padded = np.zeros((1, 24), np.int32)  # one width: causal, so the tail is inert
    padded[0, :len(seq)] = seq
    return ref_logits(params, padded, cfg)[0, len(prompt) - 1:len(seq)]


def assert_greedy(params, cfg, prompt, served):
    """Logits decide: every served token is the reference's largest logit at
    its position (float32 weights: the argmax is stable)."""
    rows = reference_rows(params, cfg, prompt, served)
    assert len(rows) == len(served)
    for tok, row in zip(served, rows):
        assert (row.max() - row[tok]) / np.abs(row).max() <= TOL
    assert list(served) == [int(np.argmax(row)) for row in rows]


def test_absorbed_decode_matches_expanded_attention_and_the_reference(small):
    """One step of the pool's decode program (absorbed form, the kernel, the
    paged cache) gives the logits the expanded full forward gives."""
    cfg, params = small
    pool = PagedDecodeSlotPool(params, cfg, slots=3, block_T=8, max_len=64)
    prompt = np.random.RandomState(5).randint(1, 256, 13).astype(np.int32)
    slot, first = pool.admit(prompt, max_new_tokens=4)
    seq = np.concatenate([prompt, [first]]).astype(np.int32)
    tokens = np.zeros((3, 1), np.int32)
    positions = np.zeros((3, 1), np.int32)
    tokens[slot, 0], positions[slot, 0] = first, len(prompt)
    got, _, stats = pool.family.decode_window(
        params, jnp.asarray(tokens), jnp.asarray(positions), pool._arenas,
        jnp.asarray(pool._tables))
    expanded = k2.forward(params, seq[None], cfg)
    close(got[slot, 0], np.asarray(expanded[0, -1]))
    close(got[slot, 0], ref_logits(params, seq[None], cfg)[0, -1])
    # one live token through two sparse layers
    assert int(stats[0]) == 2 and 0 <= int(stats[1]) <= 8  # <= 4 picks a layer


def test_pool_serves_ragged_prompts_token_for_token_with_one_decode_program(small):
    """Ragged lengths across block boundaries, a dead slot beside live ones,
    admit / retire churn: every served token is the reference's greedy token
    (float32 weights: the argmax is stable) and the decode program is traced
    once."""
    cfg, params = small
    rs = np.random.RandomState(11)
    prompts = [rs.randint(1, 256, n).astype(np.int32) for n in (7, 9, 17, 3)]
    pool = PagedDecodeSlotPool(params, cfg, slots=3, block_T=8, max_len=64)
    out = tfm.generate(params, prompts, 4, cfg, pool=pool)
    assert pool.decode_traces == 1
    for prompt, toks in zip(prompts, out):
        assert len(toks) == 4
        assert_greedy(params, cfg, prompt, toks)
    # a dead slot between two live ones, then a late admission into it
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


def test_block_stats_count_the_routing_and_the_bytes_a_token_stores(small, monkeypatch):
    from deeplearning4j_tpu.models import paged_decode

    cfg, params = small
    pool = PagedDecodeSlotPool(params, cfg, slots=2, block_T=8, max_len=64)
    pool.admit([5, 6, 7], 4)
    pool.admit([9, 10], 4)
    opened, real_span = [], paged_decode.span

    def recording_span(name, **stats):
        opened.append((name, stats))
        return real_span(name, **stats)

    monkeypatch.setattr(paged_decode, "span", recording_span)
    for _ in range(3):
        pool.step()
    # a step's routing is known when its tokens come back: the dispatch span
    # carries the counters of the step fetched last, none on the first step
    dispatch = [stats for name, stats in opened if name == "kv.step.dispatch"]
    assert set(dispatch[0]) == {"live_blocks", "mapped_blocks"}
    assert set(dispatch[1]) == {"live_blocks", "mapped_blocks", *k2.MOE_STATS}
    assert dispatch[2]["routed_tokens"] == 2 * 2  # live slots x sparse layers
    b = pool.block_stats()
    # layers x (24 values in one 128-lane tile) x float32
    assert b["kv_cache_bytes_per_token"] == 3 * 128 * 4
    assert b["moe_routed_tokens"] == 3 * 2 * 2                 # steps x live x sparse
    assert b["moe_experts_resident"] == 3 * 2 * 4
    assert 0 < b["moe_experts_touched"] <= b["moe_resident_assignments"]
    assert b["moe_resident_assignments"] == b["moe_load_sum"] <= 4 * b["moe_routed_tokens"]
    assert b["moe_load_max"] <= b["moe_load_sum"]
    assert set(pool.last_step_stats) == set(k2.MOE_STATS)
    # the family of models/transformer.py counts no experts: absent, not zero
    tcfg = tfm.TransformerConfig(vocab_size=61, d_model=16, n_heads=2, n_layers=1,
                                 d_ff=32, max_len=32, causal=True, dropout=0.0,
                                 compute_dtype=jnp.float32, attn_impl="xla")
    plain = PagedDecodeSlotPool(tfm.init_params(jax.random.key(0), tcfg), tcfg,
                                slots=2, block_T=8).block_stats()
    assert not [k for k in plain if k.startswith("moe_")]
    assert plain["kv_cache_bytes_per_token"] == 1 * 2 * 16 * 4


def test_pool_refuses_speculation_for_this_family_by_name(small):
    cfg, params = small
    with pytest.raises(ValueError, match="kimi_k2"):
        PagedDecodeSlotPool(params, cfg, slots=2, block_T=8, max_len=64,
                            draft_params=params, draft_cfg=cfg)
