"""ISSUE 35: the two prefill kernels of learned sparse attention
(``kernels/sparse_attention.py``) in interpret mode against plain numpy: the
exact k-th largest score of a row, and grouped-query attention under a
selection mask with the key blocks past the causal frontier skipped."""

import numpy as np
import pytest

import jax.numpy as jnp

from deeplearning4j_tpu.kernels.sparse_attention import kth_largest, selected_attention


@pytest.mark.parametrize("rows,T,k", [(5, 40, 8), (16, 256, 33), (3, 1000, 1),
                                      (8, 128, 128), (24, 96, 17)])
def test_kth_largest_is_the_sorted_rows_kth_value(rows, T, k):
    rs = np.random.RandomState(rows * T + k)
    x = (rs.randn(rows, T) * 10 ** rs.uniform(-6, 6, (rows, 1))).astype(np.float32)
    got = np.asarray(kth_largest(jnp.asarray(x), k))
    want = np.sort(x, axis=-1)[:, ::-1][:, k - 1:k]
    assert got.shape == (rows, 1) and np.array_equal(got, want)


def test_kth_largest_with_ties_zeros_of_both_signs_and_unseen_rows():
    inf = np.inf
    x = np.array([
        [3.0, 3.0, 3.0, 1.0, -inf, -inf, 2.0, 3.0],     # the 3rd and 4th are 3.0
        [0.0, -0.0, 0.0, -0.0, -1.0, 5.0, -0.0, 0.0],   # zeros of both signs tie
        [7.0, -inf, -inf, -inf, -inf, -inf, -inf, -inf],  # fewer than k values
        [-1e-30, -2e-30, -1e30, 1e-38, -1e-38, 0.0, 1e30, -5.0],
    ], np.float32)
    for k in (1, 3, 4, 8):
        got = np.asarray(kth_largest(jnp.asarray(x), k))[:, 0]
        want = np.sort(x, axis=-1)[:, ::-1][:, k - 1]
        assert np.array_equal(got, want), (k, got, want)  # -0.0 == 0.0
    # a leading batch shape is kept
    assert kth_largest(jnp.asarray(x).reshape(2, 2, 8), 2).shape == (2, 2, 1)


def dense_reference(q, k, v, mask, kv_heads, scale):
    C, T = mask.shape
    H = q.shape[1] // (k.shape[1] // kv_heads)
    hd = q.shape[1] // H
    qh = q.reshape(C, kv_heads, H // kv_heads, hd).astype(np.float64)
    kh = k.reshape(T, kv_heads, hd).astype(np.float64)
    vh = v.reshape(T, kv_heads, hd).astype(np.float64)
    s = np.einsum("ckgd,tkd->kgct", qh, kh) * scale
    s = np.where(mask[None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("kgct,tkd->ckgd", p, vh).reshape(C, H * hd)


@pytest.mark.parametrize("C,T,first,block_k", [
    (16, 64, 48, 16),    # the last chunk: every key block is live
    (16, 64, 16, 16),    # an early chunk: two of four key blocks are skipped
    (8, 40, 0, 16),      # T is padded up to whole key blocks
    (16, 48, 32, 512),   # one block holds every key
])
def test_selected_attention_matches_a_dense_masked_softmax(C, T, first, block_k):
    kv_heads, G, hd = 2, 4, 16
    rs = np.random.RandomState(C + T + first)
    q = rs.randn(C, kv_heads * G * hd).astype(np.float32)
    k = rs.randn(T, kv_heads * hd).astype(np.float32)
    v = rs.randn(T, kv_heads * hd).astype(np.float32)
    # a causal selection: 6 random visible keys a query, its own position
    # not always among them, keys in early blocks only for some rows
    mask = np.zeros((C, T), bool)
    for c in range(C):
        seen = first + c + 1
        mask[c, rs.choice(seen, min(6, seen), replace=False)] = True
    mask[3] = False
    mask[3, 0] = True    # a single key, in the first block
    got = selected_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                             jnp.asarray(mask), first, kv_heads=kv_heads,
                             scale=hd ** -0.5, block_k=block_k)
    want = dense_reference(q, k, v, mask, kv_heads, hd ** -0.5)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)


def test_selected_attention_never_reads_a_key_past_the_frontier():
    """Keys past ``first + C - 1`` may hold anything (a bucket's padding):
    their blocks are skipped and the rest is masked."""
    kv_heads, G, hd, C, T = 1, 2, 8, 8, 64
    rs = np.random.RandomState(0)
    q = rs.randn(C, G * hd).astype(np.float32)
    k = rs.randn(T, hd).astype(np.float32)
    v = rs.randn(T, hd).astype(np.float32)
    mask = np.tril(np.ones((C, T), bool), 8)[:, :T]      # first = 8: causal
    poisoned_k, poisoned_v = k.copy(), v.copy()
    poisoned_k[16:], poisoned_v[16:] = np.nan, np.nan
    args = dict(kv_heads=kv_heads, scale=1.0, block_k=16)
    clean = selected_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                               jnp.asarray(mask), jnp.int32(8), **args)
    dirty = selected_attention(jnp.asarray(q), jnp.asarray(poisoned_k),
                               jnp.asarray(poisoned_v), jnp.asarray(mask),
                               jnp.int32(8), **args)
    assert np.array_equal(np.asarray(clean), np.asarray(dirty))
    assert np.isfinite(np.asarray(clean)).all()
