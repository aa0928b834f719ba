"""ISSUE 37: the paged pool's cache groups. A family whose sliding-window
layers need no key more than ``window`` positions back keeps them in a group
of its own: an allocator, a table and arenas a group; a windowed group maps
at most ``window / block_T + 2`` blocks a slot, hands blocks back behind the
window, and an admission is priced in every group. The three families that
name no group are one group with ``window None`` and see none of it."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.models import keye_vl as kv
from deeplearning4j_tpu.models import kimi_k2 as k2
from deeplearning4j_tpu.models import paged_decode
from deeplearning4j_tpu.models import transformer as tfm
from deeplearning4j_tpu.models import trinity as tr
from deeplearning4j_tpu.models.paged_decode import (CacheGroup, NoFreeBlocksError,
                                                    PagedDecodeSlotPool)

WINDOW, BLOCK_T, MAX_LEN, SLOTS = 16, 8, 96, 3
CAP = WINDOW // BLOCK_T + 2          # most blocks the windowed group maps a slot
MAX_BLOCKS = MAX_LEN // BLOCK_T


@pytest.fixture(scope="module")
def model():
    cfg = tr.TrinityConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=5, num_dense_layers=1,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        intermediate_size=64, moe_intermediate_size=16, num_experts=8,
        n_resident_experts=8, num_experts_per_tok=2, sliding_window=WINDOW,
        max_position_embeddings=MAX_LEN, param_dtype=jnp.float32, attn_impl="xla",
        moe_tile=8)
    return cfg, tr.init_params(jax.random.key(0), cfg)


def prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 128, n).astype(np.int32)


def test_a_group_has_its_own_arenas_allocator_and_table(model):
    cfg, params = model
    pool = PagedDecodeSlotPool(params, cfg, slots=SLOTS, block_T=BLOCK_T, max_len=MAX_LEN)
    full, sliding = pool._groups
    assert (full.window, full.n_layers, sliding.window, sliding.n_layers) == (None, 1, WINDOW, 4)
    # by default what ``slots`` full-length requests hold at most, + the trash block
    assert pool.n_blocks == (1 + SLOTS * MAX_BLOCKS, 1 + SLOTS * CAP)
    assert [a.shape for a in pool._arenas] == (
        [(1, 1 + SLOTS * MAX_BLOCKS, BLOCK_T, 16)] * 2 + [(4, 1 + SLOTS * CAP, BLOCK_T, 16)] * 2)
    assert full.alloc is not sliding.alloc and full.tables.shape == sliding.tables.shape
    assert pool.total_blocks == SLOTS * (MAX_BLOCKS + CAP)
    b = pool.block_stats()
    assert b["blocks_total"] == b["blocks_free"] == pool.total_blocks
    assert (b["blocks_total_g0"], b["blocks_total_g1"]) == (SLOTS * MAX_BLOCKS, SLOTS * CAP)
    # K and V of one layer's 16 lanes, float32, over all five layers
    assert b["kv_cache_bytes_per_token"] == 5 * 2 * 16 * 4


@pytest.mark.parametrize("n,new,want", [
    (5, 3, (1, 1)),                 # one block in both
    (20, 12, (4, 4)),               # 32 positions: four blocks, the cap
    (40, 30, (9, 4)),               # 70 positions: nine blocks, the cap
    (90, 6, (12, 4)),               # the whole table
])
def test_a_request_is_priced_in_every_group(model, n, new, want):
    cfg, params = model
    pool = PagedDecodeSlotPool(params, cfg, slots=SLOTS, block_T=BLOCK_T, max_len=MAX_LEN)
    assert pool.request_blocks(n, new) == sum(want)
    before = pool.block_stats()
    slot, _ = pool.admit(prompt(n), new)
    after = pool.block_stats()
    assert before["blocks_free_g0"] - after["blocks_free_g0"] == want[0]
    assert before["blocks_free_g1"] - after["blocks_free_g1"] == want[1]
    pool.release(slot)
    assert pool.block_stats()["blocks_free"] == pool.total_blocks


@pytest.mark.parametrize("short", [0, 1], ids=["full_group_short", "windowed_group_short"])
def test_an_admission_waits_when_either_group_is_short(model, short):
    """Blocks for one 40 + 30 request in the short group, plenty in the other:
    the second admission is refused NOW (re-queueable), whichever group lacks
    the blocks, and fits once the first is released."""
    cfg, params = model
    need = (9, 4)
    n_blocks = [1 + SLOTS * MAX_BLOCKS, 1 + SLOTS * CAP]
    n_blocks[short] = 1 + need[short] + 1
    pool = PagedDecodeSlotPool(params, cfg, slots=SLOTS, block_T=BLOCK_T,
                               max_len=MAX_LEN, n_blocks=n_blocks)
    first, _ = pool.admit(prompt(40), 30)
    assert not pool.can_admit(prompt(40, 1), 30)
    with pytest.raises(NoFreeBlocksError, match=f"cache group {short}") as e:
        pool.admit(prompt(40, 1), 30)
    assert e.value.retry_admission
    assert pool.can_admit(prompt(3, 2), 2)            # a small one still fits
    pool.release(first)
    assert pool.can_admit(prompt(40, 1), 30)
    pool.admit(prompt(40, 1), 30)


def test_prefill_stores_only_the_rows_a_later_query_sees(model, monkeypatch):
    cfg, params = model
    pool = PagedDecodeSlotPool(params, cfg, slots=SLOTS, block_T=BLOCK_T, max_len=MAX_LEN)
    opened, real_span = [], paged_decode.span

    def recording_span(name, **stats):
        opened.append((name, stats))
        return real_span(name, **stats)

    monkeypatch.setattr(paged_decode, "span", recording_span)
    slot, _ = pool.admit(prompt(45), 8)
    full, sliding = pool._groups
    # the next query, at 45, sees keys 30 .. 44: blocks 3, 4, 5 (and 5 holds 45)
    assert list(np.flatnonzero(sliding.tables[slot])) == [3, 4, 5]
    assert list(np.flatnonzero(full.tables[slot])) == list(range(7))  # 53 positions
    prefill = next(stats for name, stats in opened if name == "kv.prefill")
    assert (prefill["rows_stored_g0"], prefill["rows_stored_g1"]) == (45, 45 - 3 * BLOCK_T)
    # 4 priced, 3 mapped: one is owed, held back from other admissions
    assert sliding.owed[slot] == 1 and sliding.alloc.reserved == 1


def test_a_step_frees_behind_the_window_and_maps_ahead(model, monkeypatch):
    """Decoding from 45 to 75: the windowed group never maps more than the
    cap, every block handed back reads 0 in the table and is free again, the
    full group keeps everything, and the dispatch span says what moved."""
    cfg, params = model
    pool = PagedDecodeSlotPool(params, cfg, slots=SLOTS, block_T=BLOCK_T, max_len=MAX_LEN)
    slot, _ = pool.admit(prompt(45), 32)
    other, _ = pool.admit(prompt(6, 1), 32)
    full, sliding = pool._groups
    opened, real_span = [], paged_decode.span

    def recording_span(name, **stats):
        opened.append((name, stats))
        return real_span(name, **stats)

    monkeypatch.setattr(paged_decode, "span", recording_span)
    for step in range(30):
        p = 45 + step                           # the position this step writes
        pool.step()
        mapped = np.flatnonzero(sliding.tables[slot])
        assert len(mapped) <= CAP
        assert mapped[0] == max(0, p - WINDOW + 1) // BLOCK_T and mapped[-1] == p // BLOCK_T
        assert list(mapped) == list(range(mapped[0], mapped[-1] + 1))
        assert sliding.owed[slot] + len(mapped) == CAP          # held + owed = the price
    assert np.flatnonzero(full.tables[slot]).size == -(-(45 + 32) // BLOCK_T)
    dispatch = [stats for name, stats in opened if name == "kv.step.dispatch"]
    assert all({"live_blocks_g0", "live_blocks_g1", "window_blocks_freed"} <= set(d)
               for d in dispatch)
    freed = sum(d["window_blocks_freed"] for d in dispatch)
    b = pool.block_stats()
    assert freed == b["kv_window_blocks_freed"] > 0
    # the short slot passed the window too (6 + 30 positions): it wrote 35 last
    assert np.flatnonzero(sliding.tables[other])[0] == (35 - WINDOW + 1) // BLOCK_T
    # rows read: the full layer everything, four sliding layers the window
    want_read = sum((45 + s + 1) + (6 + s + 1) + 4 * (min(45 + s + 1, WINDOW) + min(6 + s + 1, WINDOW))
                    for s in range(30))
    want_all = sum(5 * ((45 + s + 1) + (6 + s + 1)) for s in range(30))
    assert (b["swa_rows_read"], b["swa_rows_windowless"]) == (want_read, want_all)
    assert b["kv_blocks_read_windowed"] == sum(d["live_blocks_g1"] for d in dispatch)
    assert b["kv_blocks_read"] == sum(d["live_blocks_g0"] for d in dispatch)


def test_churn_leaks_nothing_and_traces_one_decode_program(model):
    """Admit, step, release in a shuffled order over both short and long
    requests, the windowed group sized so that admissions sometimes wait:
    every block and every reserve comes back, one decode program."""
    cfg, params = model
    pool = PagedDecodeSlotPool(params, cfg, slots=SLOTS, block_T=BLOCK_T, max_len=MAX_LEN,
                               n_blocks=(1 + 2 * MAX_BLOCKS, 1 + 2 * CAP + 1))
    rs = np.random.RandomState(3)
    live, waited, served = {}, 0, 0
    for round_ in range(40):
        n, new = int(rs.choice([3, 9, 17, 30, 50])), int(rs.randint(2, 30))
        if pool.free_slots and pool.can_admit(prompt(n, round_), new):
            slot, _ = pool.admit(prompt(n, round_), new)
            live[slot] = new - 1
        elif pool.free_slots:
            waited += 1
        for slot, toks in pool.step().items():
            live[slot] -= len(toks)
        for slot in [s for s, left in live.items() if left <= 0]:
            pool.release(slot)
            del live[slot]
            served += 1
        for g in pool._groups:
            held = sum(int((g.tables[s] > 0).sum()) for s in range(SLOTS))
            assert g.alloc.free_blocks == g.n_blocks - 1 - held - g.alloc.reserved
            assert g.alloc.reserved == int(g.owed.sum()) >= 0
    for slot in list(live):
        pool.release(slot)
    b = pool.block_stats()
    assert b["blocks_free"] == b["blocks_total"] and served > 5 and waited > 0
    assert all(g.alloc.reserved == 0 and not g.tables.any() for g in pool._groups)
    assert pool.decode_traces == 1


def test_a_failed_program_resets_every_group(model):
    cfg, params = model
    pool = PagedDecodeSlotPool(params, cfg, slots=SLOTS, block_T=BLOCK_T, max_len=MAX_LEN)
    pool.admit(prompt(40), 20)
    pool._reset_after_failure()
    assert pool.block_stats()["blocks_free"] == pool.total_blocks and pool.free_slots == SLOTS
    assert all(not g.tables.any() and g.alloc.reserved == 0 for g in pool._groups)
    slot, _ = pool.admit(prompt(40), 20)
    assert pool.step()[slot]


class _Windowed:
    """A family's answers, as far as the pool reads them at construction."""
    name, speculative, stat_names = "windowed_stub", False, ()
    n_layers, cache_widths, cache_dtype = 2, (8, 8), jnp.float32

    def __init__(self, groups, arena_groups, shares_prefix):
        self.cache_groups, self.arena_groups = groups, arena_groups
        self.shares_prefix = shares_prefix

    def resident(self, params):
        return params


@pytest.mark.parametrize("groups,arena_groups,shares,match", [
    ((CacheGroup(1), CacheGroup(1, 16)), (0, 1), True, "shares_prefix must be False"),
    ((CacheGroup(2, 16),), (0, 0), False, "its window must be None"),
    ((CacheGroup(1), CacheGroup(1, 16)), (0,), False, "cache group"),
])
def test_the_pool_refuses_groups_it_cannot_keep(groups, arena_groups, shares, match):
    class Cfg:
        causal, max_len, vocab_size = True, 64, 16

        def decode_family(self):
            return _Windowed(groups, arena_groups, shares)

    with pytest.raises(ValueError, match=match):
        PagedDecodeSlotPool({}, Cfg(), slots=2, block_T=8)


# -- the families that name no group --------------------------------------------

POOL_KEYS = {"kv_cache_bytes_per_token", "resident_weight_bytes", "blocks_total",
             "blocks_free", "cow_shared_blocks", "cow_saved_blocks", "spec_proposed",
             "spec_accepted", "kv_blocks_read", "kv_blocks_mapped",
             "kv_steps", "kv_steps_overlapped"}  # the last two: ISSUE 38
MOE_KEYS = {"moe_routed_tokens", "moe_resident_assignments", "moe_experts_touched",
            "moe_experts_resident", "moe_load_max", "moe_load_sum"}


def _transformer():
    cfg = tfm.TransformerConfig(
        vocab_size=61, max_len=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        causal=True, dropout=0.0, compute_dtype=jnp.float32, attn_impl="xla")
    return cfg, tfm.init_params(jax.random.key(0), cfg), POOL_KEYS


def _kimi():
    cfg = k2.KimiK2Config(
        vocab_size=64, hidden_size=32, num_hidden_layers=2, first_k_dense_replace=1,
        num_attention_heads=2, q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, intermediate_size=64, moe_intermediate_size=16,
        n_routed_experts=4, n_resident_experts=4, num_experts_per_tok=2,
        max_position_embeddings=64, param_dtype=jnp.float32, attn_impl="xla", moe_tile=8)
    return cfg, k2.init_params(jax.random.key(0), cfg), POOL_KEYS | MOE_KEYS


def _keye():
    cfg = kv.KeyeVLConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, head_dim=8, moe_intermediate_size=16, num_experts=4,
        n_resident_experts=4, num_experts_per_tok=2, mrope_section=(1, 1, 2),
        index_n_heads=2, index_head_dim=8, index_topk=4, index_q_chunk=8,
        max_position_embeddings=64, param_dtype=jnp.float32, moe_tile=8)
    return cfg, kv.init_params(jax.random.key(0), cfg), (
        POOL_KEYS | MOE_KEYS | {"dsa_live_rows", "dsa_selected_rows",
                                "dsa_thresholded", "dsa_tie_breaks"})


@pytest.mark.parametrize("make", [_transformer, _kimi, _keye],
                         ids=["transformer", "kimi_k2", "keye_vl"])
def test_a_family_that_names_no_group_is_one_group_and_its_stats_keys_stand(make, monkeypatch):
    cfg, params, keys = make()
    pool = PagedDecodeSlotPool(params, cfg, slots=2, block_T=8, max_len=64)
    assert len(pool._groups) == 1 and pool._groups[0].window is None
    assert pool._groups[0].n_layers == cfg.n_layers and pool.n_blocks == 1 + 2 * 8
    assert pool._alloc is pool._groups[0].alloc and pool._tables is pool._groups[0].tables
    assert pool.family.shares_prefix
    opened, real_span = [], paged_decode.span

    def recording_span(name, **stats):
        opened.append((name, stats))
        return real_span(name, **stats)

    monkeypatch.setattr(paged_decode, "span", recording_span)
    a, _ = pool.admit(np.arange(1, 20, dtype=np.int32), 6)
    b, _ = pool.admit(np.arange(1, 20, dtype=np.int32), 6)   # the same prompt: shared
    for _ in range(3):
        pool.step()
    stats = pool.block_stats()
    assert set(stats) == keys
    assert stats["cow_shared_blocks"] >= 2 and pool.decode_traces == 1
    prefill = next(s for name, s in opened if name == "kv.prefill")
    assert set(prefill) == {"bucket", "shared_blocks", "new_blocks"}
    dispatch = [s for name, s in opened if name == "kv.step.dispatch"]
    assert set(dispatch[0]) == {"live_blocks", "mapped_blocks"}
    pool.release(a), pool.release(b)
    assert pool.block_stats()["blocks_free"] == pool.total_blocks
