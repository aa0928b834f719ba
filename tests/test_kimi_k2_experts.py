"""ISSUE 31: the kimi_k2 family's decode kernel and expert layer alone, against
dense float32 references (``tests/test_kimi_k2.py`` holds the whole model and
the pool; the two files run on different workers)."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import kimi_k2 as ref
from deeplearning4j_tpu.kernels.paged_attention import paged_mla_decode_attention
from deeplearning4j_tpu.models import kimi_k2 as k2
from test_kimi_k2 import close, model_of, small_cfg

# -- (d) the kernel alone ------------------------------------------------------

BLOCK_T, MAX_BLOCKS, HEADS, C, R = 8, 20, 4, 16, 8  # 160 keys: two 128-key chunks


def dense_mla(q, arena, tables, limits, layer, scale):
    """Gather every table entry, mask past the limit, softmax, in float32."""
    S = q.shape[0]
    rows = np.asarray(arena, np.float32)[layer][np.asarray(tables)]
    rows = rows.reshape(S, -1, C + R)
    s = np.einsum("shd,std->sht", np.asarray(q, np.float32), rows) * scale
    live = np.arange(rows.shape[1])[None, None, :] < np.asarray(limits)[:, None, None]
    s = np.where(live, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    out = np.einsum("sht,stc->shc", p, rows[..., :C])
    return np.where(np.asarray(limits)[:, None, None] > 0, out, 0.0)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_mla_kernel_matches_dense_attention_through_permuted_and_shared_blocks(dtype, tol):
    rs = np.random.RandomState(2)
    lengths = [1, BLOCK_T - 1, BLOCK_T, BLOCK_T + 1, BLOCK_T * MAX_BLOCKS, 0, 133]
    S = len(lengths)
    n_blocks = 1 + S * MAX_BLOCKS
    arena = jnp.asarray(rs.randn(2, n_blocks, BLOCK_T, C + R), dtype)
    perm = rs.permutation(np.arange(1, n_blocks))
    tables = perm.reshape(S, MAX_BLOCKS).astype(np.int32)
    tables[6, :3] = tables[4, :3]        # a prefix shared with another slot
    tables[5] = 0                        # a dead slot maps nothing
    q = jnp.asarray(rs.randn(S, HEADS, C + R), dtype)
    limits = np.asarray(lengths, np.int32)
    got = paged_mla_decode_attention(q, arena, jnp.asarray(tables),
                                     jnp.asarray(limits), layer=1, scale=0.3,
                                     latent_width=C)
    want = dense_mla(q, arena, tables, limits, 1, 0.3)
    assert got.shape == (S, HEADS, C) and got.dtype == dtype
    assert np.max(np.abs(np.asarray(got, np.float32) - want)) <= tol
    assert not np.asarray(got, np.float32)[5].any()


# -- (e), (f), (g) the expert layer --------------------------------------------


def sparse_layer(cfg, params):
    return params["layers"][cfg.first_k_dense_replace]


def routed_part(cfg, p, u, live=None):
    idx, w = k2.route(cfg, p, u)
    live = jnp.ones(u.shape[0], bool) if live is None else live
    return k2.resident_experts(cfg, p, u.astype(cfg.param_dtype), idx, w, live)


@pytest.mark.parametrize("tokens,tile", [(6, 8), (37, 8)],
                         ids=["decode-sized", "prefill-sized"])
def test_the_shares_of_all_chips_add_up_to_the_uncut_layer(tokens, tile):
    """Four shares of four experts: their routed parts + the shared expert
    counted once = the reference's layer with all 16 experts."""
    whole = small_cfg(moe_tile=tile)
    params = k2.init_params(jax.random.key(1), whole)
    p = sparse_layer(whole, params)
    u = jnp.asarray(np.random.RandomState(4).randn(tokens, 64), jnp.float32)
    total = k2._swiglu(p["shared"], u)
    assignments = 0
    for share in range(4):
        cfg = dataclasses.replace(whole, expert_first=4 * share, n_resident_experts=4)
        mine = {**p, "experts": p["experts"][4 * share:4 * share + 4]}
        part, stats = routed_part(cfg, mine, u)
        total = total + part
        assignments += int(stats[1])
    assert assignments == tokens * 4  # every token-expert pair lands on one chip
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.expert_layer(p, u, model_of(whole)))
    close(total, want)


def test_routing_chooses_by_biased_scores_and_weighs_by_unbiased_ones():
    cfg = small_cfg(num_experts_per_tok=2, n_routed_experts=4, n_resident_experts=4)
    # router logits of one token: experts 0 and 1 lead; the bias lifts 3 over 1
    logits = np.asarray([[2.0, 1.0, -1.0, 0.5]], np.float32)
    u = np.zeros((1, 64), np.float32)
    u[0, 0] = 1.0
    router = np.zeros((64, 4), np.float32)
    router[0] = logits[0]
    p = {"router": jnp.asarray(router),
         "router_bias": jnp.asarray([0.0, 0.0, 0.0, 0.3], jnp.float32)}
    idx, w = k2.route(cfg, p, jnp.asarray(u))
    sc = 1.0 / (1.0 + np.exp(-logits[0]))
    assert sorted(np.argsort(-sc)[:2]) == [0, 1]           # unbiased: 0 and 1
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 3]   # biased: 3 replaces 1
    order = np.asarray(idx[0])
    want = sc[order] / sc[order].sum() * cfg.routed_scaling_factor  # no bias in it
    assert np.allclose(np.asarray(w[0]), want, rtol=1e-6)
    assert float(w.sum()) == pytest.approx(cfg.routed_scaling_factor, rel=1e-6)


def test_weights_are_normalised_over_all_chosen_resident_or_not():
    cfg = small_cfg(expert_first=0, n_resident_experts=2)
    params = k2.init_params(jax.random.key(2), cfg)
    p = sparse_layer(cfg, params)
    u = jnp.asarray(np.random.RandomState(8).randn(9, 64), jnp.float32)
    idx, w = k2.route(cfg, p, u)
    assert np.allclose(np.asarray(w.sum(-1)), cfg.routed_scaling_factor, rtol=1e-5)
    part, stats = k2.resident_experts(cfg, p, u, idx, w, jnp.ones(9, bool))
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(p, u, model_of(cfg)) - ref.swiglu(ref._f32(p["shared"]), u)
    close(part, np.asarray(want))
    assert int(stats[1]) == int(np.sum(np.asarray(idx) < 2))


@pytest.mark.parametrize("tokens,tile", [(5, 8), (29, 8)],
                         ids=["decode-sized", "prefill-sized"])
def test_no_token_is_dropped_when_every_token_picks_the_same_experts(tokens, tile):
    """The most uneven routing there is: the bias sends every token to
    experts 0..3, all resident. All tokens x 4 assignments are computed."""
    cfg = small_cfg(moe_tile=tile, n_resident_experts=6)
    params = k2.init_params(jax.random.key(5), cfg)
    p = dict(sparse_layer(cfg, params))
    p["router_bias"] = jnp.where(jnp.arange(16) < 4, 10.0, 0.0)
    u = jnp.asarray(np.random.RandomState(6).randn(tokens, 64), jnp.float32)
    part, stats = routed_part(cfg, p, u)
    assert [int(s) for s in stats] == [tokens, 4 * tokens, 4, tokens]
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(p, u, model_of(cfg)) - ref.swiglu(ref._f32(p["shared"]), u)
    close(part, np.asarray(want))


def test_dead_slots_and_padding_route_nowhere():
    cfg = small_cfg(n_resident_experts=16)
    params = k2.init_params(jax.random.key(9), cfg)
    p = sparse_layer(cfg, params)
    u = jnp.asarray(np.random.RandomState(3).randn(6, 64), jnp.float32)
    live = jnp.asarray([True, False, True, True, False, False])
    part, stats = routed_part(cfg, p, u, live)
    assert int(stats[0]) == 3 and int(stats[1]) == 12
    assert not np.asarray(part)[np.asarray(~live)].any()
    alone, _ = routed_part(cfg, p, u[np.asarray(live)])
    close(np.asarray(part)[np.asarray(live)], np.asarray(alone))


def test_the_drawn_bias_is_non_zero_and_small_beside_the_gaps_it_decides():
    """The correction bias is not trained. It is drawn non-zero, so that the
    biased choice is exercised (it changes some tokens' chosen set), and small
    (std 0.001): a bias ten times larger than the gaps between the scores at
    the boundary would choose the experts by itself, whatever the token."""
    cfg = small_cfg()
    params = k2.init_params(jax.random.key(4), cfg)
    p = sparse_layer(cfg, params)
    bias = np.asarray(p["router_bias"])
    assert bias.dtype == np.float32 and bias.shape == (cfg.n_routed_experts,)
    assert 2e-4 < bias.std() < 3e-3 and np.abs(bias).min() > 0.0
    u = jnp.asarray(np.random.RandomState(5).randn(4096, 64), jnp.float32)
    biased, _ = k2.route(cfg, p, u)
    plain, _ = k2.route(cfg, {**p, "router_bias": jnp.zeros_like(p["router_bias"])}, u)
    changed = (np.sort(np.asarray(biased), -1) != np.sort(np.asarray(plain), -1)).any(-1)
    assert 0.0 < changed.mean() < 0.25          # exercised, and not in charge
    loads = np.bincount(np.asarray(biased).ravel(), minlength=16) / biased.size
    assert np.abs(loads * 16 - 1.0).max() < 0.25  # no expert is a favourite
