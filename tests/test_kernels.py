"""Pallas/ring attention parity vs the plain-XLA reference path.

SURVEY §4.6 #4: fast-path vs reference-path parity harness (the TPU analog of
the reference's ValidateCuDNN / CuDNNGradientChecks pattern).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.kernels import flash_attention, mha_reference, ring_attention


def _qkv(shape=(2, 4, 256, 64)):
    k = jax.random.key(7)
    return [jax.random.normal(jax.random.fold_in(k, i), shape, jnp.float32) for i in range(3)]


def test_flash_matches_reference():
    q, k, v = _qkv()
    ref = mha_reference(q, k, v)
    out = flash_attention(q, k, v, block_q=128, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_causal_matches_reference():
    q, k, v = _qkv()
    ref = mha_reference(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(causal):
    q, k, v = _qkv()
    ref = mha_reference(q, k, v, causal=causal)
    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    f = jax.shard_map(
        lambda a, b, c: ring_attention(a, b, c, axis_name="sp", causal=causal),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None),
    )
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_dot_product_attention_masked_parity():
    """The front door matches the dense reference under a padding mask on
    every backend — on TPU this is the masked-flash route (small T here
    stays dense per the >=128 cutoff; flash parity is tested directly)."""
    from deeplearning4j_tpu.kernels import dot_product_attention

    q, k, v = _qkv((2, 2, 64, 32))
    mask = jnp.concatenate([jnp.ones((2, 48)), jnp.zeros((2, 16))], axis=1)
    out = dot_product_attention(q, k, v, mask)
    ref = mha_reference(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_key_padding_mask_matches_reference(causal):
    """VERDICT r4 weak #2: flash must handle BertIterator-style key padding
    masks natively instead of silently falling back to the O(T^2) path."""
    q, k, v = _qkv((2, 4, 256, 64))
    rs = np.random.RandomState(3)
    mask = jnp.asarray((rs.rand(2, 256) > 0.3).astype(np.float32))
    ref = mha_reference(q, k, v, mask, causal=causal)
    out = flash_attention(q, k, v, mask, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_masked_backward_matches_reference():
    q, k, v = _qkv((2, 2, 256, 32))
    rs = np.random.RandomState(9)
    mask = jnp.asarray((rs.rand(2, 256) > 0.25).astype(np.float32))

    gf = jax.grad(lambda *a: jnp.sum(flash_attention(*a, mask, interpret=True) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(mha_reference(*a, mask) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4)


def test_flash_fully_masked_row_matches_reference():
    """A row with zero valid keys degrades to uniform attention in BOTH paths
    (large-finite-negative convention) — no NaNs forward or backward."""
    q, k, v = _qkv((1, 2, 128, 32))
    mask = (jnp.arange(128) < 64).astype(jnp.float32)[None, :]  # keys 0-63 valid
    ref = mha_reference(q, k, v, mask)
    out = flash_attention(q, k, v, mask, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    zero_mask = jnp.zeros((1, 128))
    out2 = flash_attention(q, k, v, zero_mask, interpret=True)
    ref2 = mha_reference(q, k, v, zero_mask)
    assert np.isfinite(np.asarray(out2)).all()
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2), atol=2e-5)
    g = jax.grad(lambda *a: jnp.sum(flash_attention(*a, zero_mask, interpret=True) ** 2),
                 argnums=(0,))(q, k, v)[0]
    assert np.isfinite(np.asarray(g)).all()


def test_flash_pad_shim_dead_rows_match_reference():
    """A row with ZERO live keys degrades to uniform softmax over the
    ORIGINAL keys even when the shim pads Tk (r5 review finding: the
    uniform fallback must not average the shim's zero-keys in)."""
    q, k, v = _qkv((2, 2, 200, 32))
    mask = jnp.ones((2, 200)).at[0, :].set(0.0)  # example 0 fully masked
    ref = mha_reference(q, k, v, mask)
    out = flash_attention(q, k, v, mask, interpret=True)  # pads 200 → 256
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # causal decode with Tq > Tk: leading queries attend zero keys
    q2, k2, v2 = _qkv((1, 2, 130, 32))
    k2, v2 = k2[:, :, :70], v2[:, :, :70]
    ref2 = mha_reference(q2, k2, v2, causal=True)
    out2 = flash_attention(q2, k2, v2, causal=True, block_q=64, block_k=64,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2), atol=2e-5)


@pytest.mark.parametrize("T", [100, 130])
def test_flash_pad_shim_odd_lengths(T):
    """Non-multiple-of-block sequence lengths round up and mask the padding
    out — forward AND backward parity with the dense reference."""
    q, k, v = _qkv((2, 2, T, 32))
    rs = np.random.RandomState(T)
    mask = jnp.asarray((rs.rand(2, T) > 0.2).astype(np.float32))
    for m in (None, mask):
        ref = mha_reference(q, k, v, m)
        out = flash_attention(q, k, v, m, block_q=64, block_k=64, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    gf = jax.grad(lambda *a: jnp.sum(flash_attention(*a, mask, block_q=64,
                                                     block_k=64, interpret=True) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(mha_reference(*a, mask) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4)


def test_flash_segment_ids_block_diagonal():
    """segment_ids restrict attention to equal ids (packed sequences)."""
    q, k, v = _qkv((2, 2, 128, 32))
    segs = jnp.asarray(np.repeat([[0, 1, 2, 3]], 32, axis=1).reshape(1, 128)
                       .repeat(2, axis=0))
    dense = (segs[:, :, None] == segs[:, None, :])[:, None].astype(jnp.float32)
    ref = mha_reference(q, k, v, dense)
    out = flash_attention(q, k, v, segment_ids=segs, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # segments compose with a padding mask: padded keys drop out of their segment
    mask = jnp.ones((2, 128)).at[:, 120:].set(0.0)
    ref2 = mha_reference(q, k, v, dense * mask[:, None, None, :])
    out2 = flash_attention(q, k, v, mask, segment_ids=segs, interpret=True)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2), atol=2e-5)


def test_flash_attention_backward_parity():
    """flash_attention is differentiable (custom_vjp): grads match the
    reference-path grads. Guards the BERT train step's auto→flash path."""
    import numpy as np

    rs = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rs.randn(2, 2, 128, 16), jnp.float32) for _ in range(3))

    gf = jax.grad(lambda *a: jnp.sum(flash_attention(*a, causal=True) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(mha_reference(*a, causal=True) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_pallas_backward_matches_dense_oracle(causal, masked):
    """Blockwise Pallas backward == dense-reconstruction oracle, multi-block."""
    from deeplearning4j_tpu.kernels.attention import (
        _flash_bwd,
        _flash_bwd_dense,
        _flash_fwd,
    )

    q, k, v = _qkv((2, 2, 256, 32))
    scale = 1.0 / np.sqrt(32)
    qseg = kseg = None
    if masked:
        rs = np.random.RandomState(1)
        qseg = jnp.zeros((2, 256), jnp.int32)
        kseg = jnp.asarray(np.where(rs.rand(2, 256) > 0.3, 0, -1), jnp.int32)
    do = jax.random.normal(jax.random.key(11), q.shape, jnp.float32)
    blocks = ((128, 128),) * 3
    out, res = _flash_fwd(q, k, v, qseg, kseg, causal, scale, blocks, True, 0)
    dq, dk, dv, _, _ = _flash_bwd(causal, scale, blocks, True, 0, res, do)
    dq0, dk0, dv0 = _flash_bwd_dense(causal, scale, res, do)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(dq0), atol=3e-5)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dk0), atol=3e-5)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(dv0), atol=3e-5)


def test_flash_backward_rectangular_decode():
    """Tq != Tk (decode-with-prefix): causal offset aligns to the key end."""
    kk = jax.random.key(3)
    q = jax.random.normal(jax.random.fold_in(kk, 0), (1, 2, 64, 32), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(kk, 1), (1, 2, 256, 32), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(kk, 2), (1, 2, 256, 32), jnp.float32)
    gf = jax.grad(lambda *a: jnp.sum(flash_attention(*a, causal=True, block_q=64,
                                                     interpret=True) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(mha_reference(*a, causal=True) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_reference(causal):
    """All-to-all sequence parallelism == full attention (SURVEY §2.10 SP)."""
    from deeplearning4j_tpu.kernels import ulysses_attention

    q, k, v = _qkv((2, 4, 256, 32))
    ref = mha_reference(q, k, v, causal=causal)
    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    f = jax.shard_map(
        lambda a, b, c: ulysses_attention(a, b, c, axis_name="sp", causal=causal),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None),
    )
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ulysses_attention_respects_key_mask():
    from deeplearning4j_tpu.kernels import ulysses_attention

    q, k, v = _qkv((2, 4, 64, 16))
    rs = np.random.RandomState(5)
    mask = jnp.asarray((rs.rand(2, 64) > 0.3).astype(np.float32))
    ref = mha_reference(q, k, v, mask)
    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    f = jax.shard_map(
        lambda a, b, c, m: ulysses_attention(a, b, c, axis_name="sp", key_mask=m),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3 + (P(None, "sp"),),
        out_specs=P(None, None, "sp", None),
    )
    out = f(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ulysses_heads_divisibility_error():
    from deeplearning4j_tpu.kernels import ulysses_attention

    q, k, v = _qkv((1, 3, 64, 16))  # 3 heads, 4 devices
    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    f = jax.shard_map(
        lambda a, b, c: ulysses_attention(a, b, c, axis_name="sp"),
        mesh=mesh, in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None),
    )
    with pytest.raises(ValueError, match="divisible"):
        f(q, k, v)


def test_flash_long_t_auto_blocks_match_reference():
    """T 4096 takes the table's long-T blocks (1024 x 1024, four a side);
    numerics must match the dense reference under a mask."""
    q, k, v = _qkv((1, 2, 4096, 16))
    mask = jnp.ones((1, 4096)).at[:, 3700:].set(0.0)
    out = flash_attention(q, k, v, mask, interpret=True)
    ref = mha_reference(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_front_door_runs_per_shard_under_an_ambient_mesh():
    """A Mosaic call cannot be partitioned by GSPMD (lowering for TPU raises
    "Mosaic kernels cannot be automatically partitioned"), so under
    ``jax.sharding.set_mesh`` the front door runs the kernel in a shard_map
    over the mesh's batch and head axes. Pinned here: the jaxpr contains the
    shard_map, values and gradients match the unsharded dense reference, and
    a mesh whose axes divide neither dim still answers (replicated)."""
    from jax.sharding import NamedSharding

    from deeplearning4j_tpu.kernels import dot_product_attention

    q, k, v = _qkv((4, 4, 128, 32))
    rs = np.random.RandomState(3)
    mask = jnp.asarray((rs.rand(4, 128) > 0.2).astype(np.float32))

    def loss(attn):
        return lambda q, k, v: jnp.sum(jnp.sin(attn(q, k, v)))

    ref = jax.value_and_grad(loss(lambda q, k, v: mha_reference(q, k, v, mask)),
                             argnums=(0, 1, 2))(q, k, v)
    flash = loss(lambda q, k, v: dot_product_attention(q, k, v, mask,
                                                       impl="flash"))
    for shape in ((2, 1, 2), (1, 3, 1)):  # 3 divides neither B=4 nor H=4
        n = int(np.prod(shape))
        mesh = Mesh(np.array(jax.devices()[:n]).reshape(shape),
                    ("data", "fsdp", "tp"))
        qs, ks, vs = (jax.device_put(t, NamedSharding(mesh, P("data", "tp")))
                      for t in (q, k, v))
        with jax.sharding.set_mesh(mesh):
            assert "shard_map" in str(jax.make_jaxpr(flash)(qs, ks, vs))
            got = jax.jit(jax.value_and_grad(flash, argnums=(0, 1, 2)))(
                qs, ks, vs)
        np.testing.assert_allclose(float(got[0]), float(ref[0]), rtol=1e-5)
        for g, r in zip(got[1], ref[1]):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-5)
    # no ambient mesh: a plain call, no shard_map
    assert "shard_map" not in str(jax.make_jaxpr(flash)(q, k, v))


# -- the table's tiles and the operands' dtype (ISSUE 32) ----------------------
#
# the shapes the static table answers differently since PR 32: each kernel
# runs with the tile the table gives it, in float32 (tight) and in bf16 (bf16
# operands into the MXU, float32 statistics: the tolerance of the paged
# kernels' bf16 cases)

_TABLE_CASES = {
    # B, H, Tq, Tk, D, causal, mask
    "masked_ragged_t512": (2, 2, 512, 512, 32, False, "ragged"),
    "causal_t1024": (1, 2, 1024, 1024, 32, True, None),
    "causal_tq256_tk640_offset": (1, 2, 256, 640, 32, True, None),
    "masked_t700_pads_to_768": (2, 1, 700, 700, 32, False, "ragged"),
    "causal_masked_dead_rows_t1024": (2, 1, 1024, 1024, 16, True, "dead_rows"),
}


def _table_case(name, dtype):
    B, H, Tq, Tk, D, causal, kind = _TABLE_CASES[name]
    kk = jax.random.key(len(name))
    q = jax.random.normal(jax.random.fold_in(kk, 0), (B, H, Tq, D), jnp.float32)
    k, v = (jax.random.normal(jax.random.fold_in(kk, i), (B, H, Tk, D), jnp.float32)
            for i in (1, 2))
    do = jax.random.normal(jax.random.fold_in(kk, 3), (B, H, Tq, D), jnp.float32)
    mask = None
    if kind is not None:
        lens = np.linspace(0.6, 1.0, B) * Tk  # key padding, as the BERT cell's
        keep = np.arange(Tk)[None, :] < lens[:, None]
        if kind == "dead_rows":
            keep[0, :5] = False  # causal: rows 0-4 of example 0 see no key
            keep[1, :] = False   # example 1: no row sees any
        mask = jnp.asarray(keep.astype(np.float32))
    q, k, v, do = (t.astype(dtype) for t in (q, k, v, do))
    return q, k, v, do, mask, causal


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("name", sorted(_TABLE_CASES))
def test_flash_table_tiles_match_reference_and_dense_backward(name, dtype):
    from deeplearning4j_tpu.kernels.attention import _NEG_INF, _flash_bwd_dense

    q, k, v, do, mask, causal = _table_case(name, dtype)
    tol = dict(atol=3e-5, rtol=3e-5) if dtype == jnp.float32 else dict(atol=4e-2, rtol=4e-2)
    out, vjp = jax.vjp(lambda *a: flash_attention(*a, mask, causal=causal,
                                                  interpret=True), q, k, v)
    grads = vjp(do)
    assert out.dtype == dtype and all(g.dtype == dtype for g in grads)

    # the oracle works in float32 on the same (rounded) inputs
    qf, kf, vf, dof = (t.astype(jnp.float32) for t in (q, k, v, do))
    ref, ref_vjp = jax.vjp(lambda *a: mha_reference(*a, mask, causal=causal),
                           qf, kf, vf)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref), **tol)
    if name.startswith("causal_masked_dead_rows"):
        # a row with no live key: the dense oracle rebuilds P = 1 a key there,
        # the reference's uniform softmax is what flash_attention promises
        want = ref_vjp(dof)
    else:
        scale = 1.0 / np.sqrt(q.shape[-1])
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
        Tq, Tk = s.shape[-2:]
        qseg = kseg = None
        if causal:
            s = jnp.where(jnp.arange(Tq)[:, None] + (Tk - Tq) >= jnp.arange(Tk), s, _NEG_INF)
        if mask is not None:
            s = jnp.where(mask[:, None, None, :] > 0, s, _NEG_INF)
            qseg = jnp.zeros(mask.shape[:1] + (Tq,), jnp.int32)
            kseg = jnp.where(mask > 0, 0, -1)
        lse = jax.nn.logsumexp(s, axis=-1, keepdims=True)
        want = _flash_bwd_dense(causal, scale, (qf, kf, vf, qseg, kseg, ref, lse), dof)
    for g, w in zip(grads, want):
        assert np.isfinite(np.asarray(g, np.float32)).all()
        np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(w), **tol)


def _kernel_eqns(jaxpr):
    """Every equation inside the Pallas kernels of a jaxpr."""
    def walk(jp, inside):
        for eqn in jp.eqns:
            if inside:
                yield eqn
            for val in eqn.params.values():
                for sub in (val if isinstance(val, (list, tuple)) else [val]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from walk(sub, inside or eqn.primitive.name == "pallas_call")
    return list(walk(jaxpr.jaxpr, False))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_flash_kernels_feed_the_mxu_the_dtype_they_are_given(dtype):
    """float32 inputs: no value changes dtype anywhere in the three kernels.
    bf16 inputs: the matmuls of two loaded blocks (QK^T, dO V^T) take them as
    bf16; a matmul with P or dS takes that float32 block as it is and the
    loaded [T, D] block cast up (the MXU path rounds a float32 operand to
    bf16 itself: kernels/attention.py:_dot_f32); everything accumulates in
    float32, and no score-shaped block is ever cast."""
    T, D = 256, 32
    q, k, v = (t.astype(dtype) for t in _qkv((1, 2, T, D)))
    mask = jnp.ones((1, T)).at[:, 200:].set(0.0)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(flash_attention(*a, mask, causal=True, block_q=128, block_k=128,
                                           interpret=True).astype(jnp.float32)),
        argnums=(0, 1, 2)))(q, k, v)
    eqns = _kernel_eqns(jaxpr)
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 2 + 4 + 3  # forward, dKV, dQ
    loaded = [e for e in dots if e.outvars[0].aval.shape == (128, 128)]
    assert len(loaded) == 1 + 2 + 2  # S; S^T, dP^T; S, dP
    for e in dots:
        want = jnp.dtype(dtype if any(e is x for x in loaded) else jnp.float32)
        assert {jnp.dtype(x.aval.dtype) for x in e.invars} == {want}
        assert e.outvars[0].aval.dtype == jnp.float32
    casts = [e for e in eqns if e.primitive.name == "convert_element_type"
             and jnp.issubdtype(e.invars[0].aval.dtype, jnp.floating)
             and e.invars[0].aval.dtype != e.params["new_dtype"]]
    if dtype == jnp.float32:
        assert not casts
    else:
        assert casts and all(e.invars[0].aval.shape == (128, D) for e in casts)


# -- the forward-only kernel: grouped K/V heads, a sliding window --------------


@pytest.mark.parametrize("blocks", [(16, 16), (32, 16), (16, 32), (None, None)],
                         ids=["16x16", "32x16", "16x32", "auto"])
@pytest.mark.parametrize("window", [None, 1, 16, 40, 1000], ids=["full", "w1", "w16", "w40", "w1000"])
@pytest.mark.parametrize("heads", [(6, 2), (4, 4), (8, 1)], ids=["H6G2", "H4G4", "H8G1"])
def test_flash_window_and_grouped_heads_match_reference(heads, window, blocks):
    """``window`` against ``mha_reference`` with the mask, K/V of fewer heads
    read through the head index: blocks smaller than the window, larger, and
    windows that are not whole blocks; T 112 pads up to whole blocks."""
    H, G = heads   # (equal heads and no window: the differentiable kernels' case)
    key = jax.random.key(11)
    q = jax.random.normal(jax.random.fold_in(key, 0), (2, H, 112, 32), jnp.float32)
    k, v = (jax.random.normal(jax.random.fold_in(key, i), (2, G, 112, 32), jnp.float32)
            for i in (1, 2))
    out = flash_attention(q, k, v, causal=True, window=window, block_q=blocks[0],
                          block_k=blocks[1], interpret=True)
    ref = mha_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_window_rectangular_queries_are_the_last_positions():
    """Tq < Tk: the queries are the last Tq positions, as ``mha_reference``
    has it, and the window counts from each query's own position."""
    key = jax.random.key(5)
    q = jax.random.normal(jax.random.fold_in(key, 0), (1, 4, 32, 16), jnp.float32)
    k, v = (jax.random.normal(jax.random.fold_in(key, i), (1, 2, 96, 16), jnp.float32)
            for i in (1, 2))
    out = flash_attention(q, k, v, causal=True, window=24, block_q=16, block_k=16,
                          interpret=True)
    ref = mha_reference(q, k, v, causal=True, window=24)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_window_visits_only_the_blocks_some_query_sees():
    """The key axis of the grid is as long as the window's span in blocks,
    not as the sequence: NaN keys and values wholly behind every window of a
    q-block are never read."""
    from deeplearning4j_tpu.kernels.attention import _flash_forward_gqa

    key = jax.random.key(2)
    q = jax.random.normal(jax.random.fold_in(key, 0), (1, 2, 128, 16), jnp.float32)
    k, v = (jax.random.normal(jax.random.fold_in(key, i), (1, 1, 128, 16), jnp.float32)
            for i in (1, 2))
    jaxpr = str(jax.make_jaxpr(lambda *a: _flash_forward_gqa(
        *(x.transpose(0, 2, 1, 3) for x in a), window=32, scale=0.25, block_q=16,
        block_k=16, interpret=False, q_offset=0))(q, k, v))
    assert "grid=(2, 8, 4)" in jaxpr   # 8 q-blocks x (16 + 32 - 2) // 16 + 2 key blocks
    # the last q-block alone, against keys whose first half is NaN
    poisoned = k.at[:, :, :64].set(jnp.nan), v.at[:, :, :64].set(jnp.nan)
    out = flash_attention(q[:, :, 112:], *poisoned, causal=True, window=32,
                          block_q=16, block_k=16, interpret=True)
    ref = mha_reference(q, k, v, causal=True, window=32)[:, :, 112:]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_equal_heads_no_window_is_the_call_as_it_was(causal):
    """``window=None`` with equal heads takes the differentiable kernels, bit
    for bit what the call without the argument gives, gradients too."""
    q, k, v = _qkv((1, 2, 128, 32))
    plain = flash_attention(q, k, v, causal=causal, interpret=True)
    named = flash_attention(q, k, v, causal=causal, interpret=True, window=None)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(named))
    g = jax.grad(lambda q: flash_attention(q, k, v, causal=causal, interpret=True,
                                           window=None).sum())(q)
    assert np.isfinite(np.asarray(g)).all()


def test_flash_window_refuses_what_the_forward_only_kernel_lacks():
    q, k, v = _qkv((1, 4, 64, 16))
    with pytest.raises(ValueError, match="forward-only"):
        flash_attention(q, k, v, window=8, interpret=True)            # not causal
    with pytest.raises(ValueError, match="forward-only"):
        flash_attention(q, k[:, :2], v[:, :2], causal=True, interpret=True,
                        mask=jnp.ones((1, 64)))
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, k[:, :3], v[:, :3], causal=True, interpret=True)


def test_flash_heads_side_by_side_layout_is_the_same_attention():
    """``layout="bthd"``: q [B, T, H, D], k / v [B, T, G, D], a head a block
    of lanes of a row (no transpose on the way in or out); only the
    forward-only kernel takes it."""
    key = jax.random.key(4)
    q = jax.random.normal(jax.random.fold_in(key, 0), (2, 6, 80, 16), jnp.float32)
    k, v = (jax.random.normal(jax.random.fold_in(key, i), (2, 2, 80, 16), jnp.float32)
            for i in (1, 2))
    ref = mha_reference(q, k, v, causal=True, window=24)
    out = flash_attention(*(x.transpose(0, 2, 1, 3) for x in (q, k, v)), causal=True,
                          window=24, block_q=16, block_k=16, interpret=True, layout="bthd")
    np.testing.assert_allclose(np.asarray(out.transpose(0, 2, 1, 3)), np.asarray(ref),
                               atol=2e-5)
    with pytest.raises(ValueError, match="forward-only"):
        flash_attention(q, q, q, layout="bthd", interpret=True)      # not causal
    with pytest.raises(ValueError, match="layout"):
        flash_attention(q, k, v, causal=True, layout="hbtd", interpret=True)
