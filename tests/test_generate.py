"""Autoregressive KV-cache decode (ISSUE 13 tentpole piece 1).

The correctness contract: incremental decode through the slot pool's paged
KV cache is TOKEN-IDENTICAL to naive generation by repeated full forwards,
and membership churn in the slot pool (continuous batching's admit/retire
at step boundaries) never changes results OR mints a new decode-step XLA
signature.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from deeplearning4j_tpu.models import transformer as tfm


def _cfg(**kw):
    kw.setdefault("causal", True)
    kw.setdefault("dropout", 0.0)
    kw.setdefault("param_dtype", jnp.float32)
    kw.setdefault("compute_dtype", jnp.float32)
    kw.setdefault("attn_impl", "xla")
    kw.setdefault("vocab_size", 97)
    kw.setdefault("max_len", 64)
    kw.setdefault("d_model", 32)
    kw.setdefault("n_heads", 4)
    kw.setdefault("n_layers", 2)
    kw.setdefault("d_ff", 64)
    return tfm.TransformerConfig(**kw)


def _params(cfg, seed=0):
    import jax

    return tfm.init_params(jax.random.key(seed), cfg)


def _naive_generate(params, cfg, prompt, max_new, eos_id=None):
    """Reference: greedy decoding by re-running the FULL forward each step."""
    toks = list(int(t) for t in prompt)
    out = []
    for _ in range(max_new):
        logits = tfm.forward(params, jnp.asarray([toks], jnp.int32), cfg)
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        toks.append(nxt)
        if eos_id is not None and nxt == eos_id:
            break
    return out


def test_prefill_forward_matches_encode():
    cfg = _cfg()
    params = _params(cfg)
    toks = jnp.asarray(np.random.RandomState(0).randint(1, 97, (2, 11)),
                       jnp.int32)
    ref = tfm.encode(params, toks, cfg)
    h, ks, vs = tfm.prefill_forward(params, toks, cfg)
    np.testing.assert_allclose(np.asarray(h), np.asarray(ref), atol=1e-5)
    assert ks.shape == (cfg.n_layers, 2, cfg.n_heads, 11, cfg.head_dim)
    assert vs.shape == ks.shape


def test_incremental_decode_matches_naive_full_forward():
    """The tentpole parity pin: pool-based KV decode == repeated full
    forwards, token for token, across prompts of different lengths."""
    cfg = _cfg()
    params = _params(cfg)
    rs = np.random.RandomState(1)
    prompts = [rs.randint(1, 97, n).tolist() for n in (3, 9, 17, 5)]
    expected = [_naive_generate(params, cfg, p, 8) for p in prompts]
    got = tfm.generate(params, prompts, 8, cfg, slots=2)
    assert got == expected


def test_decode_requires_causal_config():
    cfg = _cfg(causal=False)
    with pytest.raises(ValueError, match="causal"):
        tfm.PagedDecodeSlotPool(_params(cfg), cfg, slots=2, block_T=8)


def test_slot_pool_bounds_and_validation():
    cfg = _cfg()
    pool = tfm.PagedDecodeSlotPool(_params(cfg), cfg, slots=1, max_len=16,
                                   block_T=8)
    with pytest.raises(ValueError, match="exceeds"):
        pool.admit(list(range(1, 15)), max_new_tokens=8)
    with pytest.raises(ValueError, match="at least one token"):
        pool.admit([], max_new_tokens=1)
    slot, _ = pool.admit([1, 2, 3], max_new_tokens=4)
    with pytest.raises(RuntimeError, match="no free decode slot"):
        pool.admit([4], max_new_tokens=1)
    pool.release(slot)
    with pytest.raises(ValueError, match="not active"):
        pool.release(slot)
    pool.admit([4], max_new_tokens=1)  # slot is reusable after release


def test_membership_churn_single_decode_signature_and_parity():
    """Continuous batching's enabling property: slots admit/retire while
    OTHER sequences are mid-decode, results still match naive generation,
    and the decode step never retraces (ONE XLA signature for the pool
    whatever its membership)."""
    cfg = _cfg()
    params = _params(cfg)
    rs = np.random.RandomState(2)
    long_p = rs.randint(1, 97, 4).tolist()
    short_a = rs.randint(1, 97, 6).tolist()
    short_b = rs.randint(1, 97, 2).tolist()

    pool = tfm.PagedDecodeSlotPool(params, cfg, slots=2, block_T=8)
    slot_l, first_l = pool.admit(long_p, max_new_tokens=10)
    toks_l = [first_l]
    # run the long sequence alone for 3 steps
    for _ in range(3):
        toks_l.extend(pool.step()[slot_l])
    traces_mid = pool.decode_traces
    # admit a short rider mid-flight (membership 1 -> 2)
    slot_a, first_a = pool.admit(short_a, max_new_tokens=3)
    toks_a = [first_a]
    while len(toks_a) < 3:
        out = pool.step()
        toks_l.extend(out[slot_l])
        toks_a.extend(out[slot_a])
    pool.release(slot_a)  # retire the rider (membership 2 -> 1)
    # refill the freed slot with a different sequence
    slot_b, first_b = pool.admit(short_b, max_new_tokens=2)
    toks_b = [first_b]
    while len(toks_l) < 10:
        out = pool.step()
        toks_l.extend(out[slot_l])
        if slot_b in out and len(toks_b) < 2:
            toks_b.extend(out[slot_b])
            if len(toks_b) == 2:
                pool.release(slot_b)
    pool.release(slot_l)

    assert toks_l == _naive_generate(params, cfg, long_p, 10)
    assert toks_a == _naive_generate(params, cfg, short_a, 3)
    assert toks_b == _naive_generate(params, cfg, short_b, 2)
    # the decode executable was traced exactly once, before AND after churn
    assert pool.decode_traces == 1
    assert traces_mid == 1


def test_prompt_bucketing_bounds_prefill_signatures():
    cfg = _cfg()
    params = _params(cfg)
    pool = tfm.PagedDecodeSlotPool(params, cfg, slots=4, block_T=8,
                                   min_prompt_bucket=8)
    rs = np.random.RandomState(3)
    # lengths 2..8 share the 8-bucket; 9..16 the 16-bucket
    for n in (2, 5, 8, 3):
        slot, _ = pool.admit(rs.randint(1, 97, n).tolist(), 1)
        pool.release(slot)
    assert pool.prefill_traces == 1
    slot, _ = pool.admit(rs.randint(1, 97, 12).tolist(), 1)
    pool.release(slot)
    assert pool.prefill_traces == 2
    assert pool.prompt_bucket(2) == 8
    assert pool.prompt_bucket(12) == 16
    assert pool.prompt_bucket(63) == cfg.max_len  # clamped to the cache


def test_generate_eos_stops_early():
    cfg = _cfg()
    params = _params(cfg)
    prompt = [5, 9, 2]
    ref = _naive_generate(params, cfg, prompt, 8)
    eos = ref[2]  # force an early stop at the third generated token
    out = tfm.generate(params, [prompt], 8, cfg, eos_id=eos)
    assert out == [ref[:3]]


def test_generate_validates_args():
    cfg = _cfg()
    params = _params(cfg)
    assert tfm.generate(params, [], 4, cfg) == []
    with pytest.raises(ValueError, match="max_new_tokens"):
        tfm.generate(params, [[1, 2]], 0, cfg)


def test_failed_donated_call_resets_the_pool_not_poisons_it():
    """The jitted prefill/decode fns DONATE the KV buffers: a call that
    raises after dispatch leaves them consumed, so the pool must reset
    itself (fresh cache, all slots free, KvCacheLostError with the
    all_sequences_lost marker) — one transient fault must not turn every
    later admit/step into 'Array has been deleted'."""
    cfg = _cfg()
    params = _params(cfg)
    pool = tfm.PagedDecodeSlotPool(params, cfg, slots=2, block_T=8)
    pool.admit([3, 1, 4], max_new_tokens=4)

    def boom(*a, **k):
        raise RuntimeError("injected device fault")

    real_decode = pool._decode_fn
    pool._decode_fn = boom
    with pytest.raises(tfm.KvCacheLostError) as ei:
        pool.step()
    assert ei.value.all_sequences_lost
    pool._decode_fn = real_decode
    # the pool healed: every slot free, and a fresh generation is correct
    assert pool.free_slots == pool.slots
    prompt = [5, 9, 2]
    out = tfm.generate(params, [prompt], 4, cfg, pool=pool)
    assert out == [_naive_generate(params, cfg, prompt, 4)]
