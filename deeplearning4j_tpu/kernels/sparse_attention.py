"""Learned sparse attention: two Pallas kernels for the two steps of prefill
that XLA leaves in HBM, and one for a decode step's selection.

A model with a learned selection (``models/keye_vl.py``) scores every cached
row for every query, keeps the ``k`` best and attends to those only. For a
chunk of ``C`` queries over ``T`` keys XLA computes the index scores well (one
fused matmul), but the two steps after it make several round trips through
HBM with ``[C, T]`` float32 arrays:

- :func:`kth_largest` — the ``k``-th largest score of every row, EXACT: a
  bisection over the 32 bits of the scores' order-preserving integer keys,
  with the rows' block resident in VMEM (``lax.top_k`` for this is a full
  sort of every row: 6.9 ms for ``[512, 16384]`` on a v5e, half of a 16k
  prefill). Ties are the caller's to break; the threshold is the exact value.
- :func:`selected_attention` — grouped-query flash attention of the chunk
  under the selection's mask: one (K/V head, key block) grid step holds the
  ``G`` query heads that share the K/V head, the mask tile is an int8
  ``[C, block_k]`` block shared by them, key blocks past the chunk's causal
  frontier are neither copied nor computed, and the online softmax is float32.
- :func:`decode_select` (``dsa_decode_select``) — a decode step's top-``k``
  of every slot's cached rows, exact and COMPACTED: the cells of the chosen
  rows in row order, which the step's row gather reads. A slot is one grid
  step (its scores in ``[R / 128, 128]`` vregs, its live rows a prefix): the
  k-th score by the same bisection, the rows tied at it admitted in row
  order while there is room, then each 128-row chunk packs its chosen cells
  to the left (seven lane rolls) and lands at its offset in the output (one
  more). A dead slot's step copies and computes nothing. It replaced a
  stable sort of ``[slots, max_len]`` with the cells as payload: a third of
  a step's device time (PERF.md, PR 40).

Off the TPU both run in interpret mode, as ``kernels/attention.py`` does, so
the CPU tests exercise the path the chip runs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30            # matches kernels.attention masking
_SIGN = -2 ** 31            # int32's sign bit
_VMEM_LIMIT = 64 * 2 ** 20  # of a v5e's 128 MiB; the default scope is 16


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ------------------------------------------------------------ the k-th score


def _order_keys(scores):
    """float32 -> int32 whose SIGNED order is the scores' IEEE order (the
    magnitude bits of negatives flipped)."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return bits ^ (jnp.right_shift(bits, 31) & jnp.int32(0x7FFFFFFF))


def _kth_key(keys, k: int, count):
    """The largest key ``u`` with ``count(keys >= u) >= k``, built bit by bit
    from the top: ``keys`` int32 in the scores' order (``_order_keys``),
    ``count(bool array)`` the float32 count of each group that shares a
    threshold (keepdims). The answer is built in the unsigned domain (the
    keys with the sign bit flipped), where setting a bit only ever raises it."""

    def bit(i, found):
        trial = found | jnp.left_shift(jnp.int32(1), 31 - i)
        # counted in float32: exact up to 2^24 keys a group
        return jnp.where(count(keys >= (trial ^ _SIGN)) >= k, trial, found)

    shape = jax.eval_shape(count, keys).shape
    return jax.lax.fori_loop(0, 32, bit, jnp.zeros(shape, jnp.int32)) ^ _SIGN


def _kth_kernel(keys_ref, out_ref, *, k: int):
    """One block of rows, a threshold a row."""
    out_ref[...] = _kth_key(keys_ref[...], k, lambda m: jnp.sum(
        m.astype(jnp.float32), axis=-1, keepdims=True))


@functools.partial(jax.jit, static_argnames=("k", "block_rows", "interpret"))
def _kth_call(keys, *, k: int, block_rows: int, interpret: bool):
    rows, T = keys.shape
    return pl.pallas_call(
        functools.partial(_kth_kernel, k=k),
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, T), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="dsa_kth_score",  # what a device trace calls the kernel
    )(keys)


def kth_largest(scores, k: int):
    """The ``k``-th largest value of every row of float32 ``scores``
    [..., T], exactly (``-inf`` where a row holds fewer than ``k`` values
    above it, as ``lax.top_k(scores, k)[0][..., -1]`` gives) -> [..., 1]."""
    lead, T = scores.shape[:-1], scores.shape[-1]
    keys = _order_keys(scores.reshape(-1, T))
    rows = keys.shape[0]
    # a block of rows and its double buffer stay within a few MiB of VMEM
    block = max(8, min(rows, (2 ** 22 // (4 * T)) // 8 * 8))
    pad = -rows % block
    found = _kth_call(jnp.pad(keys, ((0, pad), (0, 0))), k=k, block_rows=block,
                      interpret=_interpret())[:rows]
    back = found ^ (jnp.right_shift(found, 31) & jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(back, jnp.float32).reshape(*lead, 1)


# ----------------------------------------------- a decode step's selection


def _before(n: int):
    """bf16 [n, n]: 1 where the row's index is below the column's."""
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            < jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)).astype(jnp.bfloat16)


def _rank_in_chunk(ones):
    """bf16 0/1 [NC, 128] -> float32 [NC, 128]: how many ones come before a
    lane in its chunk. Every count here is a matmul of 0/1 bf16 (or of
    counts up to 128, exact in bf16) accumulated in float32: exact."""
    return jnp.dot(ones, _before(128), preferred_element_type=jnp.float32)


def _chunk_counts(ones):
    """bf16 0/1 [NC, 128] -> float32 [8, NC]: each chunk's count as a row
    (every sublane alike): a matmul, where a sum would need a relayout."""
    return jax.lax.dot_general(jnp.ones((8, 128), jnp.bfloat16), ones,
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _rank(ones):
    """bf16 0/1 [NC, 128] -> float32 [NC, 128]: how many ones come before a
    row, in row order."""
    counts = jnp.sum(ones.astype(jnp.float32), axis=1, keepdims=True)
    return _rank_in_chunk(ones) + jnp.dot(
        _before(ones.shape[0]).T, jnp.broadcast_to(counts, ones.shape).astype(jnp.bfloat16),
        preferred_element_type=jnp.float32)


def _select_kernel(limits_ref, visit_ref, scores_ref, cells_ref, out_ref, ties_ref,
                   acc_ref, packed_ref, offsets_ref, offsets_smem, *, k: int):
    """One slot: ``out`` [KB, 128] the cells of its selected rows in row
    order from place 0, zeros behind them; ``ties`` [1, 128] 1 where rows
    tied at the threshold outnumbered the room left for them. A slot's rows
    are ``[NC, 128]`` (row ``r`` at ``[r // 128, r % 128]``, a 128-row
    CHUNK a sublane); its live rows are the prefix below ``limits[slot]``."""
    del visit_ref                         # read by the index maps only
    limit = limits_ref[pl.program_id(0)]
    n_chunks, kb = scores_ref.shape[0], out_ref.shape[0]
    ties_ref[...] = jnp.zeros_like(ties_ref)
    lane = jax.lax.broadcasted_iota(jnp.int32, (n_chunks, 128), 1)

    def row_at(shape):
        return (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * 128
                + jax.lax.broadcasted_iota(jnp.int32, shape, 1))

    @pl.when(limit <= k)
    def _whole_prefix():                  # a dead slot too: nothing below 0
        out_ref[...] = jnp.where(row_at(out_ref.shape) < limit, cells_ref[:kb], 0)

    @pl.when(limit > k)
    def _threshold():
        keys = jnp.where(row_at(scores_ref.shape) < limit,
                         _order_keys(scores_ref[...]), _SIGN)

        def count(m):
            return jnp.sum(jnp.sum(m.astype(jnp.float32), axis=0, keepdims=True),
                           axis=1, keepdims=True)

        t = _kth_key(keys, k, count)                                # [1, 1]
        above, tied = keys > t, keys == t
        room = k - count(above)
        ties_ref[...] = jnp.broadcast_to(count(tied) > room, ties_ref.shape).astype(
            jnp.int32)
        # rows tied at t enter in row order while there is room
        chosen = above | (tied & (_rank(tied.astype(jnp.bfloat16)) < room))
        ones = chosen.astype(jnp.bfloat16)
        # within each chunk its chosen cells go to lanes 0.. in row order: a
        # chosen row moves left by the rows not chosen before it, one bit of
        # that distance a pass (lowest first: no two ever meet)
        gap = jnp.where(chosen, lane - _rank_in_chunk(ones).astype(jnp.int32), 128)
        cell = cells_ref[...]
        for bit in (1, 2, 4, 8, 16, 32, 64):
            come_gap, come_cell = (pltpu.roll(x, 128 - bit, 1) for x in (gap, cell))
            come = (come_gap & bit) != 0          # 128 (not chosen) has no such bit
            stay = (gap & bit) == 0
            cell = jnp.where(come, come_cell, cell)
            gap = jnp.where(come, come_gap, jnp.where(stay, gap, 128))
        packed_ref[...] = jnp.where(gap < 128, cell, 0)
        # where each chunk's cells start in the output: scalars, so moved to
        # SMEM in one copy (reading a vector's element costs ~0.2 us a time)
        offsets_ref[...] = jnp.dot(_chunk_counts(ones).astype(jnp.bfloat16),
                                   _before(n_chunks),
                                   preferred_element_type=jnp.float32).astype(jnp.int32)
        pltpu.sync_copy(offsets_ref, offsets_smem)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        row_lane = lane[:1]

        def chunk(c, carry):
            # lanes [start, start + count) of the output from block ``first``
            # on: the chunk's cells rolled by ``start``, the lanes that wrap
            # go to the next block (a chunk past the last chosen row adds
            # zeros to the last block)
            at = offsets_smem[0, c]
            first = jnp.minimum(at // 128, kb - 1)
            start = at % 128
            rolled = pltpu.roll(packed_ref[pl.ds(c, 1), :], start, 1)
            acc_ref[pl.ds(first, 1), :] += jnp.where(row_lane >= start, rolled, 0)
            acc_ref[pl.ds(first + 1, 1), :] += jnp.where(row_lane < start, rolled, 0)
            return carry

        # only the chunks that hold live rows
        jax.lax.fori_loop(0, jnp.minimum((limit + 127) // 128, n_chunks), chunk, 0)
        out_ref[...] = acc_ref[:kb]


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _select_call(scores, cells, limits, *, k: int, interpret: bool):
    S, n_chunks, _ = scores.shape
    kb = -(-k // 128)
    live = limits > 0
    # the slot whose rows a grid step holds: its own where it is live, else
    # the last live one before it (the first live one before any): a dead
    # slot's step starts no copy
    before = jax.lax.cummax(jnp.where(live, jnp.arange(S), -1))
    visit = jnp.where(before >= 0, before, jnp.argmax(live)).astype(jnp.int32)
    rows = pl.BlockSpec((None, n_chunks, 128), lambda i, lim, vis: (vis[i], 0, 0))
    return pl.pallas_call(
        functools.partial(_select_kernel, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[rows, rows],
            out_specs=[pl.BlockSpec((None, kb, 128), lambda i, lim, vis: (i, 0, 0)),
                       pl.BlockSpec((None, 1, 128), lambda i, lim, vis: (i, 0, 0))],
            scratch_shapes=[
                pltpu.VMEM((kb + 1, 128), jnp.int32),      # the output + 1 block
                pltpu.VMEM((n_chunks, 128), jnp.int32),    # the chunks, packed
                pltpu.VMEM((8, n_chunks), jnp.int32),      # where they start
                pltpu.SMEM((8, n_chunks), jnp.int32),
            ]),
        out_shape=[jax.ShapeDtypeStruct((S, kb, 128), jnp.int32),
                   jax.ShapeDtypeStruct((S, 1, 128), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="dsa_decode_select",  # what a device trace calls the kernel
    )(limits, visit, scores, cells)


def decode_select_counted(scores, cell_of_row, limits, k: int):
    """:func:`decode_select`, and bool [S]: the slots whose selection the
    threshold decided with more rows tied at it than room for them."""
    S, R = scores.shape
    limits = limits.astype(jnp.int32)
    chosen = jnp.arange(k)[None, :] < jnp.minimum(limits, k)[:, None]
    if k >= R:                           # every live row is selected
        cells = jnp.pad(cell_of_row, ((0, 0), (0, k - R)))
        return chosen, jnp.where(chosen, cells, 0), jnp.zeros((S,), bool)
    pad = -R % 128
    scores = jnp.pad(scores, ((0, 0), (0, pad)), constant_values=-jnp.inf)
    cells = jnp.pad(cell_of_row, ((0, 0), (0, pad)))
    out, ties = _select_call(scores.reshape(S, -1, 128), cells.reshape(S, -1, 128),
                             limits, k=k, interpret=_interpret())
    return chosen, out.reshape(S, -1)[:, :k], ties[:, 0, 0] > 0


def decode_select(scores, cell_of_row, limits, k: int):
    """One decode step's exact top-``k`` of every slot, compacted, with no
    sort: float32 ``scores`` [S, R], ``cell_of_row`` int32 [S, R] (where row
    ``r`` of slot ``s`` lies), ``limits`` [S] (slot ``s``'s live
    rows are ``0 .. limits[s] - 1``; 0 for a dead slot) -> (``chosen`` bool
    [S, k], ``cells`` int32 [S, k]). Slot ``s`` has ``min(limits[s], k)``
    places chosen, the first ones: the cells of the ``k`` rows with the
    largest scores (ties to the lower row; all live rows where there are no
    more than ``k``), in ROW order, read from ``cell_of_row``; cell 0 where
    nothing is chosen. The threshold is :func:`kth_largest`'s bisection; a
    slot is one grid step of ``dsa_decode_select``, and a dead slot's step
    copies and computes nothing."""
    return decode_select_counted(scores, cell_of_row, limits, k)[:2]


# ------------------------------------------- attention under a selection mask


def _attend_kernel(first_ref, q_ref, k_ref, v_ref, mask_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, G, hd, scale, block_k, num_k):
    """One (K/V head, key block) step: the chunk's queries of the ``G`` heads
    that share this K/V head (side by side in q's lanes) against one block of
    its keys, under the mask tile. m / l [G, C, 1], acc [C, G * hd] persist
    over the key blocks of a head (the LAST grid axis runs sequentially)."""
    j = pl.program_id(1)
    C = q_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # a key block wholly past the chunk's last query holds nothing selected
    @pl.when(j * block_k <= first_ref[0] + C - 1)
    def _accumulate():
        keep = mask_ref[...].astype(jnp.int32) != 0
        # V goes up to meet the float32 P: the MXU rounds a float32 operand
        # itself, and casting P down would cost the VPU a pass over [C, bk]
        # a head (kernels/attention.py:_dot_f32)
        k, v = k_ref[...], v_ref[...].astype(jnp.float32)
        for h in range(G):
            lanes = slice(h * hd, (h + 1) * hd)
            s = jax.lax.dot_general(q_ref[:, lanes], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            s = jnp.where(keep, s, _NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            # a row with nothing selected so far weighs its masked keys 1
            # each; the first selected key's ``corr`` wipes that out
            p = jnp.exp(s - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[:, lanes] = acc_ref[:, lanes] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(j == num_k - 1)
    def _fin():
        for h in range(G):
            lanes = slice(h * hd, (h + 1) * hd)
            o_ref[:, lanes] = (acc_ref[:, lanes] / l_ref[h]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kv_heads", "scale", "block_k",
                                             "interpret"))
def _attend_call(first, q, k, v, mask, *, kv_heads: int, scale: float,
                 block_k: int, interpret: bool):
    C, HD = q.shape
    T, KD = k.shape
    hd = KD // kv_heads
    G = HD // KD
    num_k = T // block_k

    def live(j, first_ref):
        # a dead block maps to the last live one: no new copy is started
        return jnp.minimum(j, (first_ref[0] + C - 1) // block_k)

    return pl.pallas_call(
        functools.partial(_attend_kernel, G=G, hd=hd, scale=scale,
                          block_k=block_k, num_k=num_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(kv_heads, num_k),
            in_specs=[
                pl.BlockSpec((C, G * hd), lambda g, j, f: (0, g)),
                pl.BlockSpec((block_k, hd), lambda g, j, f: (live(j, f), g)),
                pl.BlockSpec((block_k, hd), lambda g, j, f: (live(j, f), g)),
                pl.BlockSpec((C, block_k), lambda g, j, f: (0, live(j, f))),
            ],
            out_specs=pl.BlockSpec((C, G * hd), lambda g, j, f: (0, g)),
            scratch_shapes=[
                pltpu.VMEM((G, C, 1), jnp.float32),
                pltpu.VMEM((G, C, 1), jnp.float32),
                pltpu.VMEM((C, G * hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((C, HD), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="dsa_selected_attn",  # what a device trace calls the kernel
    )(first, q, k, v, mask)


def selected_attention(q, k, v, mask, first, *, kv_heads: int, scale: float,
                       block_k: int = 512):
    """softmax(q K^T * scale) V over the selected keys of each query, grouped
    query heads: q [C, H * hd] (head ``h`` in lanes ``h * hd ..``; heads
    ``g * G .. (g + 1) * G - 1`` read K/V head ``g``), k / v [T, kv_heads *
    hd], mask [C, T] (non-zero = selected; every real query selects at least
    one key, and none past its own position), ``first`` the position of the
    chunk's first query (an int or an int32 scalar): key blocks wholly past
    ``first + C - 1`` are skipped. Returns [C, H * hd] in q's dtype."""
    C, T = mask.shape
    interpret = _interpret()
    block_k = min(block_k, T)
    pad = -T % block_k
    if pad:
        k, v = (jnp.pad(x, ((0, pad), (0, 0))) for x in (k, v))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    return _attend_call(jnp.asarray(first, jnp.int32).reshape(1), q, k, v,
                        mask.astype(jnp.int8), kv_heads=kv_heads,
                        scale=float(scale), block_k=block_k, interpret=interpret)
