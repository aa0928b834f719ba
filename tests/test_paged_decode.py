"""Block-paged KV cache, CoW prefix sharing, speculative decoding (ISSUE 17).

The contracts under test: paged decode is TOKEN-IDENTICAL to naive
full-forward generation behind one decode-step signature; residency is
priced in BLOCKS at admission (the 429/400 paths fire at the door, never
mid-decode); copy-on-write prefix
sharing deduplicates physical blocks without changing any sequence's
output; and speculative decoding changes wall clock, never text.
"""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.models import transformer as tfm
from deeplearning4j_tpu.monitoring import MetricsRegistry
from deeplearning4j_tpu.serving import (GenerativeInferenceExecutor,
                                        JsonModelServer, TraceSpec)


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
    return tfm.init_params(jax.random.key(seed), cfg)


CFG = _cfg()
PARAMS = _params(CFG)
_NAIVE = {}  # prompt -> its greedy continuation under PARAMS, as far as asked
_forward = jax.jit(lambda params, tokens: tfm.forward(params, tokens, CFG))


def _naive_generate(params, cfg, prompts, max_new):
    """The reference: greedy decoding by re-running the FULL forward for
    every token, so it shares no cache code with the pool.  One program:
    rows are padded to ``max_len``, which a causal model's logits at the
    last real position cannot see (tests/test_generate.py holds the pool to
    the unpadded forward).  Continuations under the shared default model are
    computed once a module (greedy decoding is prefix-stable: a longer ask
    extends a shorter one)."""
    assert cfg is CFG
    out = []
    for prompt in prompts:
        done = _NAIVE.setdefault(tuple(prompt), []) if params is PARAMS else []
        while len(done) < max_new:
            row = np.zeros((1, CFG.max_len), np.int32)
            n = len(prompt) + len(done)
            row[0, :n] = list(prompt) + done
            done.append(int(jnp.argmax(_forward(params, row)[0, n - 1])))
        out.append(done[:max_new])
    return out


# ------------------------------------------------------------------ tentpole


def test_paged_decode_matches_naive_under_churn():
    """The parity pin: paged generation == repeated full forwards, token for
    token, over ragged prompts — and the paged decode step is traced
    exactly ONCE whatever the admission/retirement churn."""
    cfg, params = CFG, PARAMS
    rs = np.random.RandomState(1)
    prompts = [rs.randint(1, 97, n).tolist() for n in (3, 9, 17, 5, 12, 2)]
    expected = _naive_generate(params, cfg, prompts, 8)

    pool = tfm.PagedDecodeSlotPool(params, cfg, slots=3, block_T=8)
    got = tfm.generate(params, prompts, 8, cfg, pool=pool)
    assert got == expected
    # 6 sequences through 3 slots forced churn; still one XLA signature
    assert pool.decode_traces == 1
    assert pool.free_slots == pool.slots
    assert pool.block_stats()["blocks_free"] == pool.total_blocks


def test_generate_routes_through_paged_pool_by_default(monkeypatch):
    """Offline generate() without an explicit pool builds a paged pool (the
    satellite routing pin) — and the output still matches the reference."""
    cfg, params = CFG, PARAMS
    built = {}
    real = tfm.PagedDecodeSlotPool

    class Spy(real):
        def __init__(self, *a, **kw):
            built["kw"] = kw
            super().__init__(*a, **kw)

    monkeypatch.setattr(tfm, "PagedDecodeSlotPool", Spy)
    prompts = [[5, 9, 2], [7, 3]]
    out = tfm.generate(params, prompts, 6, cfg)
    assert built, "default generate() did not build a PagedDecodeSlotPool"
    assert out == _naive_generate(params, cfg, prompts, 6)


def test_block_accounting_and_admission_priced_in_blocks():
    cfg = _cfg(max_len=32)
    params = _params(cfg)
    # 9 usable blocks of 8 positions
    pool = tfm.PagedDecodeSlotPool(params, cfg, slots=8, block_T=8,
                                   n_blocks=10)
    assert pool.total_blocks == 9
    assert pool.request_blocks(5, 4) == 2  # span 9 -> 2 blocks
    # never-fits is a ValueError at the door, not a retryable 429
    with pytest.raises(ValueError, match="exceeds"):
        pool.admit(list(range(1, 30)), max_new_tokens=8)
    s0, _ = pool.admit([1, 2, 3, 4, 5], max_new_tokens=18)  # span 23 -> 3
    s1, _ = pool.admit([6, 7, 8, 9, 10], max_new_tokens=18)
    assert pool.block_stats()["blocks_free"] == 3
    # 4 blocks wanted, 3 free: retryable refusal, pool state untouched
    assert not pool.can_admit([11, 12], max_new_tokens=28)
    with pytest.raises(tfm.NoFreeBlocksError) as ei:
        pool.admit([11, 12], max_new_tokens=28)
    assert ei.value.retry_admission
    assert pool.free_slots == 6
    pool.release(s0)
    assert pool.can_admit([11, 12], max_new_tokens=28)
    pool.release(s1)
    assert pool.block_stats()["blocks_free"] == 9


def test_cow_prefix_sharing_dedups_blocks_without_changing_tokens():
    """Admissions sharing a prompt prefix map the same physical blocks
    (refcount > 1 in cow_shared_blocks) and still generate exactly what
    they would alone."""
    cfg, params = CFG, PARAMS
    rs = np.random.RandomState(3)
    prefix = rs.randint(1, 97, 16).tolist()  # two full 8-blocks
    solo_a, solo_b = _naive_generate(params, cfg,
                                     [prefix + [11, 12],
                                      prefix + [13, 14, 15]], 6)
    a, b = prefix + [11, 12], prefix + [13, 14, 15]

    pool = tfm.PagedDecodeSlotPool(params, cfg, slots=4, block_T=8)
    free0 = pool.block_stats()["blocks_free"]
    sa, fa = pool.admit(a, max_new_tokens=6)
    used_a = free0 - pool.block_stats()["blocks_free"]
    sb, fb = pool.admit(b, max_new_tokens=6)
    used_b = (free0 - used_a) - pool.block_stats()["blocks_free"]
    stats = pool.block_stats()
    assert stats["cow_shared_blocks"] == 2  # the two full prefix blocks
    assert stats["cow_saved_blocks"] >= 2
    assert used_b < used_a  # the sharer did not pay for the prefix again

    toks = {sa: [fa], sb: [fb]}
    while len(toks[sa]) < 6 or len(toks[sb]) < 6:
        for slot, new in pool.step().items():
            toks[slot].extend(new)
    pool.release(sa), pool.release(sb)
    assert toks[sa] == solo_a
    assert toks[sb] == solo_b
    assert pool.block_stats()["blocks_free"] == free0
    assert pool.block_stats()["cow_shared_blocks"] == 0


def _identity_tail_draft(params, cfg, draft_layers):
    """Zero the tail layers' residual-writing mats: pre-LN makes them exact
    no-ops, so the truncated draft predicts the target argmax exactly.
    Returns (target_params, draft_params, draft_cfg) without mutating the
    caller's tree."""
    import dataclasses

    blocks = [dict(b) for b in params["blocks"]]
    for blk in blocks[draft_layers:]:
        blk["out_w"] = jnp.zeros_like(blk["out_w"])
        blk["ffn_w2"] = jnp.zeros_like(blk["ffn_w2"])
    target_params = {"embed": params["embed"], "mlm": params["mlm"],
                     "blocks": blocks}
    draft_cfg = dataclasses.replace(cfg, n_layers=draft_layers)
    draft_params = {"embed": params["embed"], "mlm": params["mlm"],
                    "blocks": blocks[:draft_layers]}
    return target_params, draft_params, draft_cfg


@pytest.mark.parametrize("draft_kind", ["random", "identity_tail"])
def test_speculative_decode_is_token_identical(draft_kind):
    """Speculation may only change wall clock: with a draft that agrees
    with the target (acceptance ~1.0) AND one that never does (acceptance
    ~0), the emitted tokens equal plain greedy decode exactly, budgets
    clamp mid-window, and the step stays one XLA signature.  The
    identity-tail branch also pins eos-inside-an-accepted-window on the
    same compiled pool."""
    cfg = CFG
    rs = np.random.RandomState(4)
    prompts = [rs.randint(1, 97, n).tolist() for n in (3, 10, 6)]
    max_new = 7  # NOT a multiple of spec_tokens+1: pins the budget clamp
    eos_prompt = [5, 9, 2]
    if draft_kind == "identity_tail":
        params, draft_params, draft_cfg = _identity_tail_draft(PARAMS, cfg, 1)
        # greedy decode is prefix-stable, so max_new=8 covers max_new=7
        refs = _naive_generate(params, cfg, prompts + [eos_prompt], 8)
        expected, eos_ref = [r[:max_new] for r in refs[:3]], refs[3]
    else:
        params = PARAMS
        draft_cfg = _cfg(n_layers=1)
        draft_params = _params(draft_cfg, seed=9)  # unrelated weights
        expected = _naive_generate(params, cfg, prompts, max_new)

    pool = tfm.PagedDecodeSlotPool(
        params, cfg, slots=3, block_T=8,
        draft_params=draft_params, draft_cfg=draft_cfg, spec_tokens=3)
    got = tfm.generate(params, prompts, max_new, cfg, pool=pool)
    assert got == expected
    assert pool.decode_traces == 1
    stats = pool.block_stats()
    assert stats["spec_proposed"] > 0
    rate = stats["spec_accepted"] / stats["spec_proposed"]
    if draft_kind == "identity_tail":
        assert rate == pytest.approx(1.0)
        # EOS inside an accepted window retires the sequence AT the eos,
        # not at the window edge
        eos = eos_ref[2]
        cut = eos_ref.index(eos) + 1
        out = tfm.generate(params, [eos_prompt], 8, cfg, pool=pool,
                           eos_id=eos)
        assert out == [eos_ref[:cut]]
        assert pool.decode_traces == 1  # eos handling is host-side
    else:
        assert rate < 0.5  # an unrelated draft earns ~nothing


def test_failed_donated_step_resets_arena_and_executor_evicts_riders():
    """A failed donated decode call must surface KvCacheLostError with
    every rider marked lost, and leave the pool healed (fresh arena, all
    blocks free) — not poisoned with deleted buffers.  Then the same pool
    behind the EXECUTOR: a failed step evicts the riders (counted under
    reason="cache_lost"), the arena resets, and the next request
    succeeds."""
    cfg, params = CFG, PARAMS
    pool = tfm.PagedDecodeSlotPool(params, cfg, slots=2, block_T=8)
    pool.admit([3, 1, 4], max_new_tokens=4)
    pool.admit([2, 7], max_new_tokens=4)

    def boom(*a, **k):
        raise RuntimeError("injected device fault")

    real = pool._decode_fn
    pool._decode_fn = boom
    with pytest.raises(tfm.KvCacheLostError) as ei:
        pool.step()
    assert ei.value.all_sequences_lost
    pool._decode_fn = real
    assert pool.free_slots == pool.slots
    assert pool.block_stats()["blocks_free"] == pool.total_blocks
    prompt = [5, 9, 2]
    out = tfm.generate(params, [prompt], 4, cfg, pool=pool)
    assert out == _naive_generate(params, cfg, [prompt], 4)

    reg = MetricsRegistry()
    ex = GenerativeInferenceExecutor(pool, max_queue=8, registry=reg).start()
    try:
        def boom_once(*a, **k):
            pool._decode_fn = real  # fail exactly one step
            raise RuntimeError("injected device fault")

        pool._decode_fn = boom_once
        fut = ex.submit([3, 1, 4], max_new_tokens=8)
        assert fut.wait(30.0)
        assert getattr(fut.error, "all_sequences_lost", False)
        ok = ex.submit([5, 9, 2], max_new_tokens=3)
        assert ok.wait(30.0) and ok.error is None
        assert len(ok.tokens) == 3
        snap = reg.get("tdl_decode_evicted_total").snapshot()["series"]
        reasons = {tuple(s["labels"].values()): s["value"] for s in snap}
        assert reasons.get(("cache_lost",)) == 1
    finally:
        ex.stop(drain=False)


# ------------------------------------------------- admission at the door


def test_server_rejects_block_overrun_at_the_door():
    """Satellite bugfix pin: an X-Max-New-Tokens (or prompt) the block
    budget can never satisfy is a 400 AT ADMISSION — the request must not
    enter decode and get evicted mid-flight later."""
    cfg = _cfg(max_len=32)
    params = _params(cfg)
    # tiny arena: 2 usable blocks of 8, inside a 32-position max_len — the
    # BLOCK budget, not max_len, must be what refuses
    pool = tfm.PagedDecodeSlotPool(params, cfg, slots=4, block_T=8,
                                   n_blocks=3)
    server = JsonModelServer(None, generative_session=pool,
                             default_max_new_tokens=4, warmup_input=[1],
                             registry=MetricsRegistry()).start()
    try:
        assert server.wait_ready(60.0)

        def post(tokens, **headers):
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/predict",
                data=json.dumps(tokens).encode(),
                headers={"Content-Type": "application/json", **headers})
            with urllib.request.urlopen(req, timeout=15) as resp:
                return resp.status, json.loads(resp.read())

        # span 23 fits max_len but wants 3 blocks of an arena with 2: 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            post([1, 2, 3], **{"X-Max-New-Tokens": "20"})
        assert ei.value.code == 400
        assert b"KV blocks" in ei.value.read()
        assert pool.occupancy == 0  # it never touched a slot
        # a span past max_len itself still 400s with the cache message
        with pytest.raises(urllib.error.HTTPError) as ei:
            post([1, 2, 3], **{"X-Max-New-Tokens": "64"})
        assert ei.value.code == 400
        # the same budget that fits sails through
        status, out = post([1, 2, 3], **{"X-Max-New-Tokens": "4"})
        assert status == 200 and len(out["output"]) == 4

        # GET /stats exposes the block truth for capacity dashboards
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/stats", timeout=15) as r:
            stats = json.loads(r.read())["stats"]
        assert stats["blocks"]["blocks_total"] == 2
        assert stats["blocks"]["blocks_free"] == 2
    finally:
        server.stop()


def test_executor_queues_retryable_block_exhaustion():
    """Transient block exhaustion (fits the arena, just not NOW) must queue
    behind the live sequences and complete once blocks free up — not 400
    and not busy-loop."""
    cfg = _cfg(max_len=32)
    params = _params(cfg)
    pool = tfm.PagedDecodeSlotPool(params, cfg, slots=4, block_T=8,
                                   n_blocks=7)  # 6 usable blocks
    ex = GenerativeInferenceExecutor(pool, max_queue=8,
                                     registry=MetricsRegistry()).start()
    try:
        # 3 blocks each: two in flight exhaust the arena
        futs = [ex.submit([i + 1, i + 2], max_new_tokens=20)
                for i in range(3)]
        for f in futs:
            assert f.wait(60.0) and f.error is None
            assert len(f.tokens) == 20
    finally:
        ex.stop(drain=True)
    assert pool.block_stats()["blocks_free"] == 6


# ------------------------------------------------------- shared-prefix trace


def test_trace_spec_shared_prefix_mix_round_trips():
    spec = TraceSpec(duration_s=1.0, base_rate=10.0, seed=5,
                     prefix_tenants=3, prefix_len=12, suffix_len=4,
                     prompt_vocab=50)
    fn = spec.prompt_fn()
    a0, b0 = fn(0), fn(1)
    assert len(a0) == 16 and len(b0) == 16
    assert fn(0) == a0  # deterministic per index
    assert fn(3)[:12] == a0[:12]  # same tenant -> same prefix
    assert fn(3)[12:] != a0[12:]  # ...different suffix
    assert fn(1)[:12] != a0[:12]  # different tenant -> different prefix
    assert all(1 <= t < 50 for t in a0 + b0)

    clone = TraceSpec.from_dict(spec.to_dict())
    assert clone.prompt_fn()(7) == fn(7)
    # without the mix, prompt_fn is refused rather than guessing shapes
    with pytest.raises(ValueError, match="prefix_tenants"):
        TraceSpec(duration_s=1.0, base_rate=10.0).prompt_fn()


def test_trace_spec_shared_prefix_validation():
    with pytest.raises(ValueError, match="prefix_len"):
        TraceSpec(duration_s=1.0, base_rate=1.0, prefix_tenants=2,
                  prefix_len=0)
    with pytest.raises(ValueError, match="prompt_vocab"):
        TraceSpec(duration_s=1.0, base_rate=1.0, prefix_tenants=2,
                  prompt_vocab=1)


def _imports_of(path, package):
    """Every name a module's import statements (at any depth) reach, absolute."""
    import ast

    package = package.split(".")
    reached = set()
    for node in ast.walk(ast.parse(open(path).read())):
        if isinstance(node, ast.Import):
            reached.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] if node.level else []
            module = ".".join(base + ([node.module] if node.module else []))
            reached.update(f"{module}.{a.name}" for a in node.names)
    return reached


def test_the_pool_module_imports_no_model_and_no_kernel():
    """Arrows one way: ``models/transformer.py`` and ``models/kimi_k2.py``
    import the pool's module (for the arena writers); it reaches a model
    only through ``cfg.decode_family()``."""
    from deeplearning4j_tpu.models import paged_decode

    reached = _imports_of(paged_decode.__file__, paged_decode.__package__)
    assert "deeplearning4j_tpu.monitoring.trace.span" in reached  # the walk sees
    assert not [m for m in reached if m.startswith(
        ("deeplearning4j_tpu.models", "deeplearning4j_tpu.kernels"))], reached
