"""Paged decode attention (``kernels/paged_attention.py``) and what the paged
pool promises around it: the pool's in-place write of a window's rows and the
kernel (interpret mode on the CPU: the code the chip runs) against a plain
float32 reference that writes the rows in a loop, gathers, masks and
softmaxes densely; a step that leaves every block no live table names
untouched; the blocks-read / blocks-mapped counters; the benchmark's reader
of them."""

import importlib.util
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.kernels.paged_attention import paged_decode_attention
from deeplearning4j_tpu.models import transformer as tfm
from deeplearning4j_tpu.models.paged_decode import (PagedDecodeSlotPool,
                                                    _write_window)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the kernel walks a slot in chunks of 128 keys: 12 blocks of 32 are three
# chunks, so the online softmax is rescaled across chunks, the prefetch
# crosses chunk and slot boundaries, and a slot ends on any of its chunks
BLOCK_T, MAX_BLOCKS, HEAD_DIM, LAYERS, CHUNK_T = 32, 12, 64, 2, 128
MAX_LEN = BLOCK_T * MAX_BLOCKS


def dense_reference(q, k_new, v_new, k_arena, v_arena, tables, limits, layer,
                    n_heads):
    """Token w of a live slot is written at position ``limits[s, w] - 1``
    through the table (a plain loop), then softmax(q K^T / sqrt(hd)) V in
    float32 over the densely gathered logical view ``arena[layer][tables]``;
    query w sees keys < limits[s, w]. Returns (out, k_arena, v_arena)."""
    S, W, D = q.shape
    hd = D // n_heads
    block_T = k_arena.shape[2]
    tables, limits = np.asarray(tables), np.asarray(limits)
    arenas = [np.array(k_arena.astype(jnp.float32)), np.array(v_arena.astype(jnp.float32))]
    for arena, new in zip(arenas, (k_new, v_new)):
        for s in range(S):
            for w in range(W):
                pos = limits[s, w] - 1
                if pos >= 0:
                    arena[layer, tables[s, pos // block_T], pos % block_T] = \
                        np.asarray(new[s, w], np.float32)
    k = jnp.asarray(arenas[0][layer][tables].reshape(S, -1, n_heads, hd))
    v = jnp.asarray(arenas[1][layer][tables].reshape(S, -1, n_heads, hd))
    qh = q.reshape(S, W, n_heads, hd).astype(jnp.float32)
    scores = jnp.einsum("swhd,sthd->swht", qh, k,
                        precision="highest") / math.sqrt(hd)
    seen = jnp.arange(k.shape[1])[None, None, :] < limits[:, :, None]
    scores = jnp.where(seen[:, :, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("swht,sthd->swhd", p, v,
                     precision="highest").reshape(S, W, D)
    return np.asarray(out), arenas[0], arenas[1]


def make_case(n_heads, W, lengths, dtype, seed=0, max_blocks=MAX_BLOCKS):
    """Slots: one live slot per entry of ``lengths`` (None: a dead slot with a
    zero table row). Physical blocks are a random permutation; the second
    live slot shares the first's first two blocks (a joined prefix: read by
    both, written by neither unless its window lies there)."""
    rs = np.random.RandomState(seed)
    S, D = len(lengths), n_heads * HEAD_DIM
    n_blocks = 1 + S * max_blocks
    shape = (LAYERS, n_blocks, BLOCK_T, D)
    k_arena = jnp.asarray(rs.randn(*shape), dtype)
    v_arena = jnp.asarray(rs.randn(*shape), dtype)
    q, k_new, v_new = (jnp.asarray(rs.randn(S, W, D), dtype) for _ in range(3))
    tables = rs.permutation(np.arange(1, n_blocks)).reshape(
        S, max_blocks).astype(np.int32)
    limits = np.zeros((S, W), np.int32)
    live = [s for s, n in enumerate(lengths) if n is not None]
    for s, n in enumerate(lengths):
        if n is None:
            tables[s] = 0
        else:  # the window's last token sees n keys, earlier ones fewer
            limits[s] = n - np.arange(W)[::-1]
    if len(live) > 1 and min(limits[live[0], 0], limits[live[1], 0]) > 2 * BLOCK_T:
        tables[live[1], :2] = tables[live[0], :2]
    return (q, k_new, v_new, k_arena, v_arena, jnp.asarray(tables),
            jnp.asarray(limits))


def check_against_reference(args, layer, n_heads, lengths, tol):
    q, k_new, v_new, k_arena, v_arena, tables, limits = args
    k_arena = _write_window(k_arena, layer, tables, limits, k_new)
    v_arena = _write_window(v_arena, layer, tables, limits, v_new)
    out = paged_decode_attention(q, k_arena, v_arena, tables, limits,
                                 layer=layer, n_heads=n_heads)
    ref, ref_k, ref_v = dense_reference(*args, layer, n_heads)
    assert out.shape == ref.shape and out.dtype == args[0].dtype
    live = [s for s, n in enumerate(lengths) if n is not None]
    dead = [s for s, n in enumerate(lengths) if n is None]
    np.testing.assert_allclose(np.asarray(out, np.float32)[live], ref[live],
                               atol=tol, rtol=tol)
    # a dead slot is never visited: it must not fault, reads as zeros, and
    # nothing of it is written; a live slot's rows land in their cells and
    # every other byte of the arenas is what it was
    assert not np.asarray(out, np.float32)[dead].any()
    np.testing.assert_array_equal(np.asarray(k_arena, np.float32), ref_k)
    np.testing.assert_array_equal(np.asarray(v_arena, np.float32), ref_v)


# the operands are float32 and the CPU's dots are full float32: kernel and
# reference differ only in the ORDER of float32 sums (online softmax over
# 128-key chunks, 1280-lane contractions), a few ulp of values of order 1
F32_TOL = 2e-5
# bf16 operands: the kernel rounds the softmax weights to bf16 before p @ V
# and its result to bf16 (2^-9 relative each, on sums of order 1-4)
BF16_TOL = 4e-2


@pytest.mark.parametrize("n_heads", [20, 2], ids=["H20", "H2"])
@pytest.mark.parametrize("W", [1, 5], ids=["W1", "W5"])
@pytest.mark.parametrize(
    "length", [1, BLOCK_T - 1, BLOCK_T, BLOCK_T + 1, CHUNK_T - 1, CHUNK_T,
               CHUNK_T + 1, 2 * CHUNK_T + 1, MAX_LEN],
    ids=["len1", "lenB-1", "lenB", "lenB+1", "lenC-1", "lenC", "lenC+1",
         "len2C+1", "lenMax"])
def test_kernel_matches_dense_float32_reference(length, W, n_heads):
    """Ragged lengths around a block boundary and around a chunk boundary (the
    window of W tokens ENDS at ``length``, so with W 5 it straddles the
    boundary too: its queries end on different chunks), a dead slot between
    live ones, permuted physical blocks with a shared prefix, per-query
    limits; the last slot always spans three chunks."""
    lengths = [max(length, W), None, MAX_LEN - 7]
    args = make_case(n_heads, W, lengths, jnp.float32, seed=length * 10 + W)
    check_against_reference(args, 1, n_heads, lengths, F32_TOL)


@pytest.mark.parametrize("W", [1, 5], ids=["W1", "W5"])
def test_kernel_in_bfloat16_at_the_cells_shape(W):
    """The cell's dtype and table width: bf16 operands, float32 accumulation
    and softmax, 20 heads, 32 blocks a slot (``max_len`` 1024, eight chunks):
    a slot that fills its table, one that ends inside its fifth chunk, a dead
    one, a chat-sized one and the shortest there is."""
    lengths = [32 * BLOCK_T, 4 * CHUNK_T + 88, None, 120, W]
    args = make_case(20, W, lengths, jnp.bfloat16, seed=7, max_blocks=32)
    check_against_reference(args, 1, 20, lengths, BF16_TOL)


def test_kernel_refuses_mismatched_shapes():
    q, k_new, v_new, k_arena, v_arena, tables, limits = make_case(
        2, 1, [3, 9], jnp.float32)
    with pytest.raises(ValueError, match="do not match"):
        paged_decode_attention(q, k_arena, v_arena, tables, limits,
                               layer=0, n_heads=3)
    with pytest.raises(ValueError, match="do not match"):
        paged_decode_attention(q, k_arena, v_arena[:, :-1], tables, limits,
                               layer=0, n_heads=2)
    with pytest.raises(ValueError, match="do not match"):
        paged_decode_attention(q, k_arena, v_arena, tables[:1], limits,
                               layer=0, n_heads=2)


# -- grouped heads and a first visible key --------------------------------------


def grouped_reference(q, k_arena, v_arena, tables, limits, starts, layer,
                      n_heads, kv_heads):
    """softmax(q K^T / sqrt(hd)) V in float32 over the densely gathered view:
    query head h against K/V head ``h // (n_heads / kv_heads)``, query w of
    slot s over keys ``starts[s, w] .. limits[s, w] - 1``."""
    S, W, _ = q.shape
    hd = k_arena.shape[-1] // kv_heads
    tables, limits, starts = (np.asarray(x) for x in (tables, limits, starts))
    k = np.asarray(k_arena.astype(jnp.float32))[layer][tables].reshape(S, -1, kv_heads, hd)
    v = np.asarray(v_arena.astype(jnp.float32))[layer][tables].reshape(S, -1, kv_heads, hd)
    k, v = (np.repeat(x, n_heads // kv_heads, axis=2) for x in (k, v))
    qh = np.asarray(q, np.float32).reshape(S, W, n_heads, hd)
    scores = np.einsum("swhd,sthd->swht", qh, k) / math.sqrt(hd)
    at = np.arange(k.shape[1])[None, None, :]
    seen = (at < limits[:, :, None]) & (at >= starts[:, :, None])
    scores = np.where(seen[:, :, None, :], scores, -1e30)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("swht,sthd->swhd", p, v).reshape(S, W, n_heads * hd)


def make_grouped_case(n_heads, kv_heads, W, lengths, window, dtype, seed=0,
                      free_behind=False):
    """As ``make_case`` with arenas of ``kv_heads * HEAD_DIM`` lanes; query w
    starts at ``max(0, position - window + 1)`` (None: at 0).
    ``free_behind``: every block wholly before a slot's earliest start is
    unmapped (0: the trash block, filled with NaN), as the pool leaves it
    after handing the block back."""
    rs = np.random.RandomState(seed)
    S = len(lengths)
    n_blocks = 1 + S * MAX_BLOCKS
    shape = (LAYERS, n_blocks, BLOCK_T, kv_heads * HEAD_DIM)
    k_arena, v_arena = (rs.randn(*shape).astype(np.float32) for _ in range(2))
    if free_behind:
        k_arena[:, 0] = v_arena[:, 0] = np.nan
    q = jnp.asarray(rs.randn(S, W, n_heads * HEAD_DIM), dtype)
    tables = rs.permutation(np.arange(1, n_blocks)).reshape(S, MAX_BLOCKS).astype(np.int32)
    limits = np.zeros((S, W), np.int32)
    for s, n in enumerate(lengths):
        if n is None:
            tables[s] = 0
        else:
            limits[s] = n - np.arange(W)[::-1]
    starts = (np.zeros_like(limits) if window is None
              else np.maximum(limits - window, 0))
    if free_behind:
        for s, n in enumerate(lengths):
            if n is not None:
                tables[s, :starts[s].min() // BLOCK_T] = 0
    return (q, jnp.asarray(k_arena, dtype), jnp.asarray(v_arena, dtype),
            jnp.asarray(tables), jnp.asarray(limits), jnp.asarray(starts))


@pytest.mark.parametrize("window", [None, 1, BLOCK_T, CHUNK_T + 5, 200],
                         ids=["full", "w1", "wB", "wC+5", "w200"])
@pytest.mark.parametrize("W", [1, 3], ids=["W1", "W3"])
@pytest.mark.parametrize("heads", [(6, 2), (4, 4), (8, 1)],
                         ids=["H6G2", "H4G4", "H8G1"])
def test_grouped_heads_and_starts_match_a_dense_softmax(heads, W, window):
    """Grouped query heads over fewer K/V heads, and a first visible key a
    query: slots that end inside their first, second and third chunk and one
    that fills its table, a dead slot; every block behind a slot's window is
    UNMAPPED (the trash block holds NaN: a kernel that looks there fails)."""
    H, G = heads
    lengths = [max(W, 5), CHUNK_T + 3, None, 2 * CHUNK_T + 40, MAX_LEN]
    q, k_arena, v_arena, tables, limits, starts = make_grouped_case(
        H, G, W, lengths, window, jnp.float32, seed=H * 7 + W,
        free_behind=window is not None)
    out = paged_decode_attention(
        q, k_arena, v_arena, tables, limits, layer=1, n_heads=H, kv_heads=G,
        starts=None if window is None else starts)
    clean = (jnp.nan_to_num(k_arena), jnp.nan_to_num(v_arena))
    ref = grouped_reference(q, *clean, tables, limits, starts, 1, H, G)
    live = [s for s, n in enumerate(lengths) if n is not None]
    np.testing.assert_allclose(np.asarray(out)[live], ref[live],
                               atol=F32_TOL, rtol=F32_TOL)
    assert not np.asarray(out)[2].any()


def test_grouped_heads_in_bfloat16_at_a_served_shape():
    """48 query heads over 8 K/V heads of 128 lanes, bf16, a window of 160
    keys over slots of up to 384: the widths a grouped, windowed model is
    served at (table width cut to the test's)."""
    rs = np.random.RandomState(3)
    H, G, hd, S = 48, 8, 128, 3
    n_blocks = 1 + S * MAX_BLOCKS
    k_arena, v_arena = (jnp.asarray(rs.randn(1, n_blocks, BLOCK_T, G * hd), jnp.bfloat16)
                        for _ in range(2))
    q = jnp.asarray(rs.randn(S, 1, H * hd), jnp.bfloat16)
    tables = jnp.asarray(rs.permutation(np.arange(1, n_blocks)).reshape(S, MAX_BLOCKS), jnp.int32)
    limits = jnp.asarray([[MAX_LEN], [0], [77]], jnp.int32)
    starts = jnp.maximum(limits - 160, 0)
    tables = tables.at[1].set(0)
    out = paged_decode_attention(q, k_arena, v_arena, tables, limits, layer=0,
                                 n_heads=H, kv_heads=G, starts=starts)
    ref = grouped_reference(q, k_arena, v_arena, tables, limits, starts, 0, H, G)
    np.testing.assert_allclose(np.asarray(out, np.float32)[[0, 2]], ref[[0, 2]],
                               atol=BF16_TOL, rtol=BF16_TOL)


@pytest.mark.parametrize("W", [1, 5], ids=["W1", "W5"])
def test_equal_heads_and_no_starts_are_the_call_as_it_was(W):
    """``kv_heads == n_heads`` named or not, and ``starts`` of zeros against
    none at all, give the same BITS: the flags add nothing to the path a
    model with its own K/V a head decodes through."""
    lengths = [CHUNK_T + 9, None, MAX_LEN - 7]
    q, _, _, k_arena, v_arena, tables, limits = make_case(4, W, lengths, jnp.float32, seed=W)
    plain = paged_decode_attention(q, k_arena, v_arena, tables, limits, layer=1, n_heads=4)
    named = paged_decode_attention(q, k_arena, v_arena, tables, limits, layer=1,
                                   n_heads=4, kv_heads=4)
    zeros = paged_decode_attention(q, k_arena, v_arena, tables, limits, layer=1,
                                   n_heads=4, starts=jnp.zeros_like(limits))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(named))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(zeros))


def test_kernel_names_what_it_accepts_when_it_refuses():
    q, k_arena, v_arena, tables, limits, starts = make_grouped_case(
        6, 2, 1, [9, 40], 16, jnp.float32)
    with pytest.raises(ValueError, match="kv_heads"):
        paged_decode_attention(q, k_arena, v_arena, tables, limits, layer=0, n_heads=6)
    with pytest.raises(ValueError, match="kv_heads"):
        paged_decode_attention(q, k_arena, v_arena, tables, limits, layer=0,
                               n_heads=6, kv_heads=4)
    with pytest.raises(ValueError, match="starts"):
        paged_decode_attention(q, k_arena, v_arena, tables, limits, layer=0,
                               n_heads=6, kv_heads=2, starts=starts[:1])


# -- the pool around the kernel ------------------------------------------------


def _small_model():
    cfg = tfm.TransformerConfig(
        vocab_size=61, max_len=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        causal=True, dropout=0.0, compute_dtype=jnp.float32, attn_impl="xla")
    return tfm.init_params(jax.random.key(0), cfg), cfg


def _small_pool():
    return PagedDecodeSlotPool(*_small_model(), slots=3, block_T=8)


def test_step_writes_only_the_cells_of_live_windows():
    """In place means in place: after a step every block that no live table
    names is byte-identical (the trash block too: nothing of a dead slot is
    written), and so is every cell of a named block except the one position
    each live slot wrote."""
    pool = _small_pool()
    rs = np.random.RandomState(3)
    # stale K/V everywhere, as a long-running server's arena holds
    pool._arenas = tuple(jnp.asarray(rs.randn(*a.shape), a.dtype)
                         for a in pool._arenas)
    a, _ = pool.admit(rs.randint(1, 61, 11).tolist(), max_new_tokens=6)
    b, _ = pool.admit(rs.randint(1, 61, 5).tolist(), max_new_tokens=6)
    c, _ = pool.admit(rs.randint(1, 61, 20).tolist(), max_new_tokens=6)
    pool.release(b)  # a dead slot between two live ones
    for _ in range(3):
        before = [np.asarray(a).copy() for a in pool._arenas]
        written = {(int(pool._tables[s, pool._positions[s] // pool.block_T]),
                    int(pool._positions[s] % pool.block_T)) for s in (a, c)}
        pool.step()
        for old, new in zip(before, map(np.asarray, pool._arenas)):
            changed = np.argwhere((old != new).any(axis=(0, 3)))  # (block, cell)
            assert {(int(blk), int(cell)) for blk, cell in changed} == written


def test_blocks_read_and_mapped_count_what_the_tables_say():
    """``kv_blocks_read`` is, a step, the blocks up to each LIVE slot's
    window; ``kv_blocks_mapped`` is ``slots x max_blocks`` a step — under
    admit / retire churn, with one decode program."""
    pool = _small_pool()
    rs = np.random.RandomState(5)
    assert pool.block_stats()["kv_blocks_read"] == 0
    assert pool.block_stats()["kv_blocks_mapped"] == 0
    read = steps = 0

    def step():
        nonlocal read, steps
        live = np.flatnonzero(pool._active)
        read += sum(-(-(int(pool._positions[s]) + 1) // pool.block_T) for s in live)
        steps += 1
        pool.step()

    a, _ = pool.admit(rs.randint(1, 61, 7).tolist(), max_new_tokens=9)   # 1 block
    step()
    step()                                                              # -> 2 blocks
    b, _ = pool.admit(rs.randint(1, 61, 30).tolist(), max_new_tokens=4)  # 4 blocks
    step()
    pool.release(a)
    step()
    c, _ = pool.admit(rs.randint(1, 61, 16).tolist(), max_new_tokens=3)  # 3 blocks
    step()
    pool.release(b)
    pool.release(c)
    assert pool.step() == {}  # nothing live: no program ran, nothing counted
    stats = pool.block_stats()
    assert read == 1 + 2 + (2 + 4) + 4 + (5 + 3)  # b crossed into its fifth block
    assert stats["kv_blocks_read"] == read
    assert stats["kv_blocks_mapped"] == steps * pool.slots * pool.max_blocks
    assert pool.decode_traces == 1


def test_speculative_window_counts_blocks_up_to_its_last_position():
    params, cfg = _small_model()
    pool = PagedDecodeSlotPool(params, cfg, slots=2, block_T=8,
                               draft_params=params, draft_cfg=cfg, spec_tokens=3)
    pool.admit(list(range(1, 7)), max_new_tokens=8)  # position 6, window 6..9
    pool.step()
    assert pool.block_stats()["kv_blocks_read"] == 2  # positions 0..9: two blocks
    assert pool.block_stats()["kv_blocks_mapped"] == 2 * pool.max_blocks


# -- the benchmark's reader ------------------------------------------------------


def _read(metric, obs):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    spec = importlib.util.spec_from_file_location(
        "metric_under_test_" + metric.replace(".", "_"),
        os.path.join(ROOT, "benchmark", "metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(obs)


@pytest.mark.parametrize("obs,expected", [
    ({"serve": {"executor_stats": {"blocks": {
        "kv_blocks_read": 300, "kv_blocks_mapped": 2048, "blocks_total": 512}}}},
     100.0 * 300 / 2048),
    # the parent commit's pool counts neither: the line leaves the metric out
    ({"serve": {"executor_stats": {"blocks": {"blocks_total": 512}}}}, None),
    ({"serve": {"executor_stats": {"steps": 3}}}, None),
    ({"serve": {"executor_stats": {"blocks": {
        "kv_blocks_read": 0, "kv_blocks_mapped": 0}}}}, None),
    ({"serve": None}, None),   # a training cell
    ({}, None),
], ids=["share", "no-counters", "no-blocks", "no-steps", "train", "empty"])
def test_read_block_share_reader(obs, expected):
    got = _read("kv.read_block_share", obs)
    if expected is None:
        assert got is None
    else:
        assert got == pytest.approx(expected, rel=1e-12)
