"""Paged decode attention: Pallas kernels that read the cache through the
block tables, live blocks only. One kernel a family of layers:
``paged_decode_attention`` for heads with their own K and V (below), and
``paged_mla_decode_attention`` for absorbed latent attention (at the end).

The paged pool (``models/paged_decode.py``) keeps K and V in arenas
``[L, n_blocks, block_T, H*hd]`` — a block is one contiguous, lane-dense
``[block_T, H*hd]`` tile holding every head — and gives each slot a row of
``tables`` mapping logical block -> physical block. A decode step brings
``W`` tokens a slot (1 plain, ``spec_tokens + 1`` in a verify pass): token
``w`` of slot ``s`` sits at position ``limits[s, w] - 1`` and its query sees
the slot's first ``limits[s, w]`` keys. The pool has written the window's own
K/V into those cells before the call (write-before-read: a stale cell at an
attended position never survives a step); the kernel only READS the arenas.
A dead slot has limit 0: it costs no DMA and no compute, and its output is
zeros.

What the kernel moves is proportional to live tokens. The wrapper turns the
limits into a flat work list of (slot, chunk) items, a chunk being the
``chunk_T`` keys of consecutive logical blocks; the kernel walks the list in
one loop, copies only the blocks a slot's longest query reaches from HBM
(double-buffered, one item ahead, across slot boundaries) and accumulates an
online softmax in float32. All heads of a slot go through the MXU at once:
the query rows are laid out block-diagonally (row ``h`` keeps head ``h``'s
lanes, zero elsewhere), so ``q2 @ K^T`` gives per-head scores without
reshaping the ``H*hd`` lanes, and the per-head output is the matching
diagonal block of ``p @ V``. Operands stay in the arena's dtype (bf16 on the
chip) with float32 accumulation; scale, mask and softmax are float32.

**Grouped heads** (``kv_heads``): a model whose ``n_heads`` query heads share
``kv_heads`` K/V heads keeps arenas of ``kv_heads * hd`` lanes, and query head
``h`` reads the lanes of K/V head ``h // (n_heads / kv_heads)``: the same
block-diagonal layout over those lanes (a query's heads come in as rows
``[Hp, hd]`` and are laid into their K/V head's lanes in VMEM; the output is
read back from there). **A first visible key** (``starts [S, W]``, a sliding
window's): a slot's work list begins at the block of its earliest start, the
chunks mask below each query's own start, and a block before it is never
looked up in the table, so the pool may hand it back and leave the entry
unmapped. With ``kv_heads == n_heads`` and no ``starts`` the kernel is what it
was: seven scalar lists, ``[S*W, H*hd]`` query rows.

Off the TPU the same kernel runs in interpret mode (as
``kernels/attention.py:flash_attention`` does), so the CPU tests exercise
the path the chip runs.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30   # matches kernels.attention masking
_CHUNK_T = 128     # keys per work item: one lane tile of scores


def _kernel(*refs, W, H, G, hd, Hp, bT, C, MB, scale, windowed):
    """Walk the (slot, chunk) work list; see the module docstring.

    k_hbm/v_hbm [L, NB, bT, D], left in HBM, D = G*hd lanes; kbuf/vbuf
    [2, C*bT, D]; q2_ref [W*Hp, D]; m/l [W*Hp, 1]; acc [W*Hp, D]. With as
    many K/V heads as query heads (G == H) q_ref/o_ref are [S*W, D] float32
    (rows of a slot are consecutive); grouped, they are [S*W*Hp, hd]: a
    query's heads are rows. ``windowed``: two more scalar lists, the first
    visible key of every query and the slot's first block, from which its
    chunks count."""
    if windowed:
        (layer_ref, tables_ref, limits_ref, nblk_ref, wslot_ref, wchunk_ref,
         nwork_ref, starts_ref, fblk_ref, *refs) = refs
    else:
        (layer_ref, tables_ref, limits_ref, nblk_ref, wslot_ref, wchunk_ref,
         nwork_ref, *refs) = refs
    (q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, q2_ref, m_ref, l_ref,
     acc_ref) = refs
    D = G * hd
    T = C * bT
    layer = layer_ref[0]
    n_work = nwork_ref[0]

    def first_block(s):
        return fblk_ref[s] if windowed else 0

    # dead slots are never visited: their rows must still be defined. Blocks
    # of a chunk past a slot's live length are not copied either, so the
    # buffers start finite (masked scores are exactly 0 weight, and 0 x NaN
    # would not be)
    o_ref[...] = jnp.zeros_like(o_ref)
    kbuf[...] = jnp.zeros_like(kbuf)
    vbuf[...] = jnp.zeros_like(vbuf)

    def block_copies(i, buf, op):
        """``start`` or ``wait`` the copies of work item i's live blocks (K
        and V, at most C each) into half ``buf`` of the buffers."""
        s, c = wslot_ref[i], wchunk_ref[i]
        for j in range(C):
            lb = first_block(s) + c * C + j
            phys = tables_ref[s * MB + jnp.minimum(lb, MB - 1)]
            dst = pl.ds(j * bT, bT)

            @pl.when(lb < nblk_ref[s])
            def _():
                for n, (hbm, vmem) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                    getattr(pltpu.make_async_copy(
                        hbm.at[layer, phys], vmem.at[buf, dst],
                        sems.at[n, buf]), op)()

    if G == H:
        # row h of a query's Hp rows owns lanes [h*hd, (h+1)*hd); rows >= H
        # own none
        row = jax.lax.broadcasted_iota(jnp.int32, (Hp, D), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (Hp, D), 1)
        own = (lane >= row * hd) & (lane < (row + 1) * hd)
    else:
        # grouped: row h reads the lanes of K/V head h // (H / G), so its
        # head's hd values go there and its output comes back from there
        group = jax.lax.broadcasted_iota(jnp.int32, (Hp, hd), 0) // (H // G)

    @pl.when(n_work > 0)
    def _():
        block_copies(0, 0, "start")

    def item(i, carry):
        buf = i % 2
        s, c = wslot_ref[i], wchunk_ref[i]

        @pl.when(i + 1 < n_work)
        def _():
            block_copies(i + 1, 1 - buf, "start")

        @pl.when(c == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)
            for w in range(W):
                if G == H:
                    qw = q_ref[pl.ds(s * W + w, 1), :]                # [1, D]
                    q2 = jnp.where(own, qw, 0.0)
                else:
                    qh = q_ref[pl.ds(pl.multiple_of((s * W + w) * Hp, Hp), Hp), :]
                    q2 = jnp.concatenate(
                        [jnp.where(group == g, qh, 0.0) for g in range(G)], axis=1)
                q2_ref[w * Hp:(w + 1) * Hp, :] = q2.astype(q2_ref.dtype)

        block_copies(i, buf, "wait")
        k = kbuf[buf]                                                  # [T, D]
        v = vbuf[buf]
        sc = jax.lax.dot_general(q2_ref[...], k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        kpos = (first_block(s) * bT + c * T
                + jax.lax.broadcasted_iota(jnp.int32, (Hp, T), 1))

        def seen(w):
            below = kpos < limits_ref[s * W + w]
            return below & (kpos >= starts_ref[s * W + w]) if windowed else below

        sc = jnp.concatenate(
            [jnp.where(seen(w), sc[w * Hp:(w + 1) * Hp], _NEG_INF)
             for w in range(W)], axis=0)                               # [N, T]
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

        @pl.when(first_block(s) + (c + 1) * C >= nblk_ref[s])
        def _():
            o = acc_ref[...] / l_ref[...]                              # [N, D]
            for w in range(W):
                ow = o[w * Hp:(w + 1) * Hp]
                if G == H:
                    o_ref[pl.ds(s * W + w, 1), :] = jnp.sum(
                        jnp.where(own, ow, 0.0), axis=0, keepdims=True)
                else:
                    o_ref[pl.ds(pl.multiple_of((s * W + w) * Hp, Hp), Hp), :] = sum(
                        jnp.where(group == g, ow[:, g * hd:(g + 1) * hd], 0.0)
                        for g in range(G))

        return carry

    jax.lax.fori_loop(0, n_work, item, 0)


def _work_list(limits, block_T: int, C: int, max_items: int, starts=None):
    """(nblk [S], work_slot, work_chunk [max_items], n_work [1]) from the
    per-query limits: slot s is visited ``ceil(nblk[s] / C)`` times, in slot
    order; entries past ``n_work`` are never read. With ``starts`` (the first
    visible key of every query) a slot's chunks count from the block of its
    earliest one, ``fblk [S]``, which is returned as a fifth value."""
    S = limits.shape[0]
    nblk = -(-jnp.max(limits, axis=1) // block_T)
    fblk = (0 if starts is None
            else jnp.minimum(jnp.min(starts, axis=1) // block_T, nblk))
    nchunk = -(-(nblk - fblk) // C)
    ends = jnp.cumsum(nchunk)
    i = jnp.arange(max_items, dtype=jnp.int32)
    slot = jnp.minimum(jnp.sum(i[:, None] >= ends[None, :], axis=1), S - 1)
    chunk = i - (ends - nchunk)[slot]
    out = (nblk.astype(jnp.int32), slot.astype(jnp.int32),
           chunk.astype(jnp.int32), ends[-1:].astype(jnp.int32))
    return out if starts is None else (*out, fblk.astype(jnp.int32))


def paged_decode_attention(q, k_arena, v_arena, tables, limits, *, layer,
                           n_heads: int, kv_heads: Optional[int] = None,
                           starts=None):
    """Attend each slot's live keys through its block table:
    softmax(q K^T / sqrt(hd)) V.

    q: [S, W, H*hd] — W tokens a slot. k_arena, v_arena:
    [L, n_blocks, block_T, kv_heads*hd], the window's own K/V already in
    their cells; ``layer`` (an int or an int32 scalar) picks the layer.
    ``kv_heads`` (default ``n_heads``) must divide ``n_heads``: query head
    ``h`` reads the lanes of K/V head ``h // (n_heads / kv_heads)``. tables:
    [S, max_blocks] int32, logical -> physical block. limits: [S, W] int32 —
    query w of slot s attends keys ``starts[s, w] .. limits[s, w] - 1``
    (``starts`` [S, W] int32, default 0: a sliding window's first visible
    key; blocks before a slot's earliest start are never looked up, so their
    table entries may be unmapped); limit 0 for every w marks a dead slot.
    Returns out [S, W, H*hd] in q's dtype (a dead slot's rows are zeros)."""
    S, W, D = q.shape
    G = n_heads if kv_heads is None else kv_heads
    if (D % n_heads or n_heads % G or k_arena.shape[-1] != G * (D // n_heads)
            or v_arena.shape != k_arena.shape):
        raise ValueError(
            f"q {q.shape} of {n_heads} heads over kv_heads={G} K/V heads do not "
            f"match arenas {k_arena.shape}, {v_arena.shape}: an arena holds "
            f"kv_heads * head_dim lanes, and kv_heads divides n_heads")
    if tables.shape[0] != S or limits.shape != (S, W) or (
            starts is not None and starts.shape != (S, W)):
        raise ValueError(f"tables {tables.shape} / limits {limits.shape} / starts "
                         f"{None if starts is None else starts.shape} do not "
                         f"match q {q.shape}")
    # off the TPU the same kernel is emulated, as kernels/attention.py does
    return _paged_call(q, k_arena, v_arena, tables, limits,
                       jnp.asarray(layer, jnp.int32).reshape(1), starts,
                       n_heads=n_heads, kv_heads=G,
                       interpret=jax.default_backend() != "tpu")


# jitted with the layer as DATA: a model's layers share one trace and one
# Mosaic lowering of the kernel (traced a layer, 36 of them cost a served
# model 7 s of set-up before any compile cache is asked)
@functools.partial(jax.jit, static_argnames=("n_heads", "kv_heads", "interpret"))
def _paged_call(q, k_arena, v_arena, tables, limits, layer, starts=None, *,
                n_heads: int, kv_heads: int, interpret: bool):
    S, W, _ = q.shape
    bT, D = k_arena.shape[2], k_arena.shape[3]
    H, G, MB = n_heads, kv_heads, tables.shape[1]
    hd = D // G
    C = max(1, min(_CHUNK_T // bT, MB))          # blocks per work item
    Hp = -(-H // 16) * 16                        # whole bf16 sublane tiles
    max_items = S * -(-MB // C)
    windowed = starts is not None
    scalars = _work_list(limits, bT, C, max_items, starts)
    if windowed:   # (.., nwork, starts, fblk): the kernel's order
        scalars = (*scalars[:4], starts.reshape(-1).astype(jnp.int32), scalars[4])

    kernel = functools.partial(
        _kernel, W=W, H=H, G=G, hd=hd, Hp=Hp, bT=bT, C=C, MB=MB,
        scale=1.0 / math.sqrt(hd), windowed=windowed)
    if G == H:
        rows = pl.BlockSpec((S * W, D), lambda i, *_: (0, 0))
        qrows = q.reshape(S * W, D)
    else:  # a query's heads as rows, up to whole sublane tiles
        rows = pl.BlockSpec((S * W * Hp, hd), lambda i, *_: (0, 0))
        qrows = jnp.pad(q.reshape(S * W, H, hd), ((0, 0), (0, Hp - H), (0, 0))
                        ).reshape(S * W * Hp, hd)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 + len(scalars),
            grid=(1,),
            in_specs=[rows, hbm, hbm],
            out_specs=rows,
            scratch_shapes=[
                pltpu.VMEM((2, C * bT, D), k_arena.dtype),
                pltpu.VMEM((2, C * bT, D), v_arena.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((W * Hp, D), k_arena.dtype),
                pltpu.VMEM((W * Hp, 1), jnp.float32),
                pltpu.VMEM((W * Hp, 1), jnp.float32),
                pltpu.VMEM((W * Hp, D), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct(qrows.shape, jnp.float32),
        interpret=interpret,
        name="paged_decode_attn",  # what a device trace calls the kernel
    )(layer, tables.reshape(-1).astype(jnp.int32),
      limits.reshape(-1).astype(jnp.int32), *scalars,
      qrows.astype(jnp.float32), k_arena, v_arena)
    if G != H:
        out = out.reshape(S * W, Hp, hd)[:, :H]
    return out.reshape(S, W, H * hd).astype(q.dtype)


# ------------------------------------------------- absorbed latent attention
#
# A latent (MLA) model caches ONE row a token, ``[c | kr]``: the latent that
# every head's keys and values are made from, then the rotary key they share.
# In the absorbed form a head's query is already in that space
# (``[q_nope Wuk^T | q_rope]``), so a slot's attention is its H query rows
# against one ``[keys, C + R]`` tile whose first C lanes are also the values:
# no block-diagonal layout, one key tile for all heads. Work list, prefetch,
# masking and the online softmax are the kernel's above.


def _mla_kernel(layer_ref, tables_ref, limits_ref, nblk_ref, wslot_ref,
                wchunk_ref, nwork_ref, q_ref, kv_hbm, o_ref, kvbuf, sems,
                m_ref, l_ref, acc_ref, *, H, C, bT, NC, MB, scale):
    """q_ref [S*H, C+R] (a slot's heads are consecutive rows); kv_hbm
    [L, NB, bT, C+R], left in HBM; o_ref [S*H, C]; kvbuf [2, NC*bT, C+R];
    m/l [H, 1]; acc [H, C]."""
    T = NC * bT
    layer = layer_ref[0]
    n_work = nwork_ref[0]

    # as above: dead slots are never visited, and blocks past a slot's live
    # length are not copied, so rows and buffers start defined and finite
    o_ref[...] = jnp.zeros_like(o_ref)
    kvbuf[...] = jnp.zeros_like(kvbuf)

    def block_copies(i, buf, op):
        s, c = wslot_ref[i], wchunk_ref[i]
        for j in range(NC):
            lb = c * NC + j
            phys = tables_ref[s * MB + jnp.minimum(lb, MB - 1)]

            @pl.when(lb < nblk_ref[s])
            def _():
                getattr(pltpu.make_async_copy(
                    kv_hbm.at[layer, phys], kvbuf.at[buf, pl.ds(j * bT, bT)],
                    sems.at[buf]), op)()

    @pl.when(n_work > 0)
    def _():
        block_copies(0, 0, "start")

    def item(i, carry):
        buf = i % 2
        s, c = wslot_ref[i], wchunk_ref[i]

        @pl.when(i + 1 < n_work)
        def _():
            block_copies(i + 1, 1 - buf, "start")

        @pl.when(c == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        block_copies(i, buf, "wait")
        kv = kvbuf[buf]                                            # [T, C+R]
        q = q_ref[pl.ds(pl.multiple_of(s * H, H), H), :]           # [H, C+R]
        sc = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        kpos = c * T + jax.lax.broadcasted_iota(jnp.int32, (H, T), 1)
        sc = jnp.where(kpos < limits_ref[s], sc, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, :C], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

        @pl.when((c + 1) * NC >= nblk_ref[s])
        def _():
            o_ref[pl.ds(pl.multiple_of(s * H, H), H), :] = (
                acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

        return carry

    jax.lax.fori_loop(0, n_work, item, 0)


def paged_mla_decode_attention(q, arena, tables, limits, *, layer, scale: float,
                               latent_width: int):
    """Absorbed latent attention of one decode step through the block tables:
    ``softmax(q K^T * scale) K[:, :latent_width]`` over each slot's live rows.

    q: [S, H, C + R], a slot's H heads already in the cache's space
    (``[q_nope Wuk^T | q_rope]``). arena: [L, n_blocks, block_T, C + R], the
    step's own rows already in their cells; ``layer`` picks the layer.
    tables: [S, max_blocks] int32. limits: [S] int32: slot s attends rows
    ``0 .. limits[s] - 1``; 0 marks a dead slot. Returns [S, H, C] in q's
    dtype (a dead slot's rows are zeros)."""
    S, H, D = q.shape
    if D != arena.shape[-1] or not 0 < latent_width <= D:
        raise ValueError(f"q {q.shape} / latent width {latent_width} do not "
                         f"match the arena {arena.shape}")
    if tables.shape[0] != S or limits.shape != (S,):
        raise ValueError(f"tables {tables.shape} / limits {limits.shape} do "
                         f"not match q {q.shape}")
    return _mla_call(q, arena, tables, limits,
                     jnp.asarray(layer, jnp.int32).reshape(1),
                     scale=float(scale), latent_width=latent_width,
                     interpret=jax.default_backend() != "tpu")


@functools.partial(jax.jit,
                   static_argnames=("scale", "latent_width", "interpret"))
def _mla_call(q, arena, tables, limits, layer, *, scale: float,
              latent_width: int, interpret: bool):
    S, H, D = q.shape
    bT, MB, C = arena.shape[2], tables.shape[1], latent_width
    NC = max(1, min(_CHUNK_T // bT, MB))         # blocks per work item
    max_items = S * -(-MB // NC)
    nblk, wslot, wchunk, nwork = _work_list(limits[:, None], bT, NC, max_items)

    kernel = functools.partial(_mla_kernel, H=H, C=C, bT=bT, NC=NC, MB=MB,
                               scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(1,),
            in_specs=[pl.BlockSpec((S * H, D), lambda i, *_: (0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((S * H, C), lambda i, *_: (0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, NC * bT, D), arena.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, C), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((S * H, C), q.dtype),
        # every slot's queries and outputs stay in VMEM for the whole call:
        # 64 slots x 64 heads x (576 + 512) values is past the default limit
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="paged_mla_decode_attn",  # what a device trace calls the kernel
    )(layer, tables.reshape(-1).astype(jnp.int32), limits.astype(jnp.int32),
      nblk, wslot, wchunk, nwork, q.reshape(S * H, D).astype(arena.dtype), arena)
    return out.reshape(S, H, C)
