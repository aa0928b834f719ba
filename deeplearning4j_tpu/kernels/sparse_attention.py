"""Learned sparse attention over whole sequences (prefill): two Pallas kernels
for the two steps that XLA leaves in HBM.

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


def _kth_kernel(keys_ref, out_ref, *, k: int):
    """One block of rows: the largest ``u`` with ``count(keys >= u) >= k``,
    built bit by bit from the top. ``keys`` are int32 whose SIGNED order is
    the scores' order; the answer is built in the unsigned domain (the keys
    with the sign bit flipped), where setting a bit only ever raises it."""
    keys = keys_ref[...]

    def bit(i, found):
        trial = found | jnp.left_shift(jnp.int32(1), 31 - i)
        # counted in float32: exact up to 2^24 keys a row
        enough = jnp.sum((keys >= (trial ^ _SIGN)).astype(jnp.float32),
                         axis=-1, keepdims=True) >= k
        return jnp.where(enough, trial, found)

    found = jax.lax.fori_loop(0, 32, bit, jnp.zeros(out_ref.shape, jnp.int32))
    out_ref[...] = found ^ _SIGN


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
    bits = jax.lax.bitcast_convert_type(scores.reshape(-1, T), jnp.int32)
    # IEEE order as signed integer order: flip the magnitude of negatives
    keys = bits ^ (jnp.right_shift(bits, 31) & jnp.int32(0x7FFFFFFF))
    rows = keys.shape[0]
    # a block of rows and its double buffer stay within a few MiB of VMEM
    block = max(8, min(rows, (2 ** 22 // (4 * T)) // 8 * 8))
    pad = -rows % block
    found = _kth_call(jnp.pad(keys, ((0, pad), (0, 0))), k=k, block_rows=block,
                      interpret=_interpret())[:rows]
    back = found ^ (jnp.right_shift(found, 31) & jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(back, jnp.float32).reshape(*lead, 1)


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
