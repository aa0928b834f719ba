"""ISSUE 34: the slot pool serves from weights cast ONCE. Its family maps the
caller's (float32 master) params to a resident tree whose matmul operands are
already in the compute dtype; the one ``_layer`` / ``mlm_head`` then cast
nothing in the step, and training, which hands them the masters, is untouched.

The contracts: (a) every program gives the SAME BITS from the resident tree as
from the masters cast inside the step (the parent's path); (b) a leaf already
in its dtype is the same array, never a copy; (c) ``block_stats()`` counts the
resident bytes; (d) the train step lowers to the text it lowered to before.
"""

import dataclasses
import hashlib
import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.models import kimi_k2 as k2
from deeplearning4j_tpu.models import transformer as tfm
from deeplearning4j_tpu.models.paged_decode import PagedDecodeSlotPool
from deeplearning4j_tpu.nn.updaters import Adam

SLOTS, BLOCK_T, MAX_LEN = 3, 8, 64


def _cfg(norm_position="pre", **kw):
    """bf16 compute over float32 masters: the dtypes of the served cells."""
    return tfm.TransformerConfig(
        vocab_size=97, max_len=MAX_LEN, d_model=32, n_heads=4, n_layers=2,
        d_ff=64, causal=True, dropout=0.0, norm_position=norm_position,
        attn_impl="xla", **kw)


def _masters(cfg, seed=0):
    """Random float32 weights, biases and norm gains drawn too (at 0 and 1 a
    bias or gain that went through another dtype would not show)."""
    leaves, tree = jax.tree.flatten(tfm.init_params(jax.random.key(seed), cfg))
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    return jax.tree.unflatten(tree, [
        x + 0.1 * jax.random.normal(k, x.shape, x.dtype) if x.ndim == 1 else x
        for x, k in zip(leaves, keys)])


def _cast_in_the_step(masters):
    """The parent's path through today's family: every leaf float32, so
    ``_layer`` and ``mlm_head`` cast each one inside the program."""
    return {**masters, "head": {"tok": masters["embed"]["tok"]}}


def _same_bits(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(np.asarray(g.astype(jnp.float32)),
                              np.asarray(w.astype(jnp.float32)))


# -- (a) the same bits ---------------------------------------------------------


def _run(program, fam, params):
    """One of the family's three programs, jitted as the pool jits them."""
    rs = np.random.RandomState(3)
    if program == "prefill":
        tokens = rs.randint(1, 97, (1, 16)).astype(np.int32)
        return jax.jit(fam.prefill)(params, tokens, np.int32(11))
    if program == "head":
        h = rs.standard_normal((5, 32)).astype(np.float32)
        return jax.jit(fam.head)(params, jnp.asarray(h, jnp.bfloat16))
    # a two-token window of three slots (one dead) over arenas that already
    # hold rows, through tables that map two blocks a slot
    n_blocks = 1 + SLOTS * 2
    arenas = tuple(jnp.asarray(
        rs.standard_normal((fam.n_layers, n_blocks, BLOCK_T, w)), fam.cache_dtype)
        for w in fam.cache_widths)
    tables = np.array([[1, 2], [3, 4], [0, 0]], np.int32)
    tokens = rs.randint(1, 97, (SLOTS, 2)).astype(np.int32)
    positions = np.array([[5, 6], [9, 10], [0, 1]], np.int32)
    logits, arenas, stats = jax.jit(fam.decode_window)(
        params, tokens, positions, arenas, tables)
    assert stats is None
    return logits, arenas


@pytest.mark.parametrize("norm_position", ["pre", "post"])
@pytest.mark.parametrize("program", ["decode_window", "prefill", "head"])
def test_a_program_reads_the_same_bits_from_the_resident_tree(program, norm_position):
    cfg = _cfg(norm_position)
    fam, masters = cfg.decode_family(), _masters(cfg)
    resident = fam.resident(masters)
    assert resident["blocks"][0]["qkv_w"].dtype == jnp.bfloat16  # it engaged
    _same_bits(_run(program, fam, resident),
               _run(program, fam, _cast_in_the_step(masters)))


def _draft(cfg):
    """A one-layer draft from the same zoo, with weights of its own."""
    dcfg = dataclasses.replace(cfg, n_layers=1)
    return dcfg, _masters(dcfg, seed=5)


@pytest.mark.parametrize("speculative", [False, True], ids=["plain", "speculative"])
def test_a_pool_from_masters_generates_what_the_cast_in_the_step_did(speculative):
    """3 prompts x 8 steps: the tokens and every arena, bit for bit, from a
    pool that serves its resident copy and from one made to run the parent's
    path (its programs handed the float32 masters)."""
    cfg = _cfg()
    masters = _masters(cfg)
    kw = dict(slots=SLOTS, block_T=BLOCK_T)
    if speculative:
        dcfg, dmasters = _draft(cfg)
        kw.update(draft_params=dmasters, draft_cfg=dcfg, spec_tokens=2)
    pool = PagedDecodeSlotPool(masters, cfg, **kw)
    parent = PagedDecodeSlotPool(masters, cfg, **kw)
    parent.params = _cast_in_the_step(masters)
    if speculative:
        parent.draft_params = _cast_in_the_step(dmasters)
    rs = np.random.RandomState(2)
    prompts = [rs.randint(1, 97, n).tolist() for n in (3, 9, 17)]
    got = {}
    for p in (pool, parent):
        slots = [p.admit(prompt, 20) for prompt in prompts]
        toks = {slot: [first] for slot, first in slots}
        for _ in range(8):
            for slot, step_toks in p.step().items():
                toks[slot].extend(step_toks)
        got[p] = toks
    assert got[pool] == got[parent]
    assert all(len(t) >= 9 for t in got[pool].values())
    _same_bits(pool._arenas + pool._draft_arenas,
               parent._arenas + parent._draft_arenas)
    if speculative:
        assert pool.spec_proposed == parent.spec_proposed > 0
        assert pool.spec_accepted == parent.spec_accepted


# -- (b) a leaf in its dtype is the same array ---------------------------------


def _assert_every_leaf_is_one_passed_in(pool_params, passed):
    theirs = {id(x) for x in jax.tree.leaves(passed)}
    leaves = jax.tree.leaves(pool_params)
    assert leaves and all(id(x) in theirs for x in leaves)


def test_a_tree_in_the_compute_dtype_passes_through_by_identity():
    cfg = _cfg(param_dtype=jnp.bfloat16)
    params = tfm.init_params(jax.random.key(0), cfg)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(params))
    pool = PagedDecodeSlotPool(params, cfg, slots=SLOTS, block_T=BLOCK_T)
    _assert_every_leaf_is_one_passed_in(pool.params, params)
    # the head's view of the table IS the lookup's
    assert pool.params["head"]["tok"] is params["embed"]["tok"]


def test_the_latent_familys_tree_is_the_callers():
    cfg = k2.KimiK2Config(
        vocab_size=256, hidden_size=64, num_hidden_layers=2,
        first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=32,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        intermediate_size=128, moe_intermediate_size=32, n_routed_experts=16,
        expert_first=4, n_resident_experts=4, num_experts_per_tok=4,
        max_position_embeddings=MAX_LEN, attn_impl="xla", moe_tile=8)
    params = k2.init_params(jax.random.key(7), cfg)
    pool = PagedDecodeSlotPool(params, cfg, slots=SLOTS, block_T=BLOCK_T,
                               max_len=MAX_LEN)
    assert pool.params is params
    _assert_every_leaf_is_one_passed_in(pool.params, params)
    assert pool.block_stats()["resident_weight_bytes"] == sum(
        x.nbytes for x in jax.tree.leaves(params))


def test_masters_pass_through_where_a_step_reads_them_in_float32():
    cfg = _cfg()
    masters = _masters(cfg)
    resident = cfg.decode_family().resident(masters)
    assert resident["embed"] is masters["embed"]   # the lookup's float32 rows
    for name in ("ln_scale", "ln_bias", "out_bias"):
        assert resident["mlm"][name] is masters["mlm"][name]
    for p, m in zip(resident["blocks"], masters["blocks"]):
        assert set(p) == set(m)
        for name, x in p.items():
            if name.startswith("ln"):
                assert x is m[name]
            else:
                assert x.dtype == jnp.bfloat16 and m[name].dtype == jnp.float32
    assert resident["head"]["tok"].dtype == jnp.bfloat16
    assert resident["mlm"]["w"].dtype == resident["mlm"]["b"].dtype == jnp.bfloat16


def test_the_mapping_works_on_shapes():
    cfg = _cfg()
    shapes = jax.eval_shape(lambda: tfm.init_params(jax.random.key(0), cfg))
    fam = cfg.decode_family()
    got = fam.resident(shapes)
    want = jax.eval_shape(fam.resident, shapes)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert isinstance(g, jax.ShapeDtypeStruct)
        assert (g.shape, g.dtype) == (w.shape, w.dtype)


# -- (c) the counter -----------------------------------------------------------


@pytest.mark.parametrize("param_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32-in", "bf16-in"])
def test_block_stats_counts_the_resident_bytes(param_dtype):
    cfg = _cfg(param_dtype=param_dtype)
    params = tfm.init_params(jax.random.key(0), cfg)
    dcfg = dataclasses.replace(cfg, n_layers=1)
    dparams = tfm.init_params(jax.random.key(1), dcfg)
    pool = PagedDecodeSlotPool(params, cfg, slots=SLOTS, block_T=BLOCK_T)
    distinct = {id(x): x.nbytes for x in jax.tree.leaves(pool.params)}
    assert pool.block_stats()["resident_weight_bytes"] == sum(distinct.values())
    given = sum(x.nbytes for x in jax.tree.leaves(params))
    table = params["embed"]["tok"].nbytes
    if param_dtype == jnp.float32:
        # matmul leaves halved, and the table twice: float32 for the lookup,
        # the compute dtype for the head
        kept = sum(x.nbytes for x in jax.tree.leaves(params["embed"])) + sum(
            p[k].nbytes for p in params["blocks"] + [params["mlm"]]
            for k in p if k.startswith(("ln", "out_bias")))
        assert sum(distinct.values()) == kept + (given - kept) // 2 + table // 2
    else:
        assert sum(distinct.values()) == given  # one table, shared by two views
    spec = PagedDecodeSlotPool(params, cfg, slots=SLOTS, block_T=BLOCK_T,
                               draft_params=dparams, draft_cfg=dcfg)
    both = {id(x): x.nbytes for x in jax.tree.leaves((spec.params, spec.draft_params))}
    assert spec.block_stats()["resident_weight_bytes"] == sum(both.values())
    assert sum(both.values()) > sum(distinct.values())


# -- (d) training is handed the masters, and lowers to the parent's text -------

# sha256 of ``jit(make_train_step).lower(...).as_text()`` (no locations in it)
# at PR 33's commit 5eec039, under this container's jax 0.9.0 on the CPU. A PR
# that means to change the train step writes the new digests here.
TRAIN_STEP_TEXT = {
    ("post", False): "1acac8084e2948ae12a7463d7d7b231e6a9cf3e09be339cc8f5176e58e279a9d",
    ("pre", True): "85202266afb14dccc0b1c308565f449fe5c1d5ca5befd21584e61c3a3af7f2b7",
}


@pytest.mark.parametrize("norm_position,causal", list(TRAIN_STEP_TEXT),
                         ids=["post-norm-bidirectional", "pre-norm-causal"])
def test_the_train_step_lowers_to_the_parents_text(norm_position, causal):
    cfg = tfm.TransformerConfig(
        vocab_size=101, max_len=32, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        causal=causal, norm_position=norm_position, dropout=0.0,
        gelu_approximate=causal, attn_impl="xla")
    updater = Adam(1e-4)
    params = jax.eval_shape(lambda: tfm.init_params(jax.random.key(0), cfg))
    tok = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    batch = {"tokens": tok, "labels": tok,
             "weights": jax.ShapeDtypeStruct((2, 32), jnp.float32)}
    if not causal:
        batch["pad_mask"] = jax.ShapeDtypeStruct((2, 32), jnp.float32)
    step = jax.jit(tfm.make_train_step(cfg, updater), donate_argnums=(0, 1))
    text = step.lower(params, jax.eval_shape(updater.init, params), batch,
                      jax.ShapeDtypeStruct((), jnp.int32),
                      jax.eval_shape(lambda: jax.random.key(0))).as_text()
    assert "loc(" not in text
    # the masters go in as float32 and every matmul operand is cast in the step
    assert "tensor<32x96xf32>" in text and "tensor<32x96xbf16>" in text
    assert hashlib.sha256(text.encode()).hexdigest() == \
        TRAIN_STEP_TEXT[norm_position, causal]


# -- the reader ``benchmark/metrics/kv.resident_weight_bytes.py`` --------------

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(metric, obs):
    spec = importlib.util.spec_from_file_location(
        "metric_under_test_" + metric.replace(".", "_"),
        os.path.join(ROOT, "benchmark", "metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(obs)


@pytest.mark.parametrize("obs,expected", [
    # ``/stats`` after the window, from a pool that counts its resident bytes
    ({"serve": {"executor_stats": {"blocks": {
        "resident_weight_bytes": 1_812_541_588, "kv_blocks_read": 3,
        "kv_blocks_mapped": 9}}}}, 1_812_541_588),
    # the parent's pool: blocks, but not this counter
    ({"serve": {"executor_stats": {"blocks": {"kv_blocks_read": 3,
                                              "kv_blocks_mapped": 9}}}}, None),
    # a session without ``block_stats``, a training cell, nothing at all
    ({"serve": {"executor_stats": {"steps": 5}}}, None),
    ({"serve": None, "train": {}}, None),
    ({}, None),
], ids=["counted", "parent", "no-blocks", "training", "empty"])
def test_the_reader_on_a_hand_made_observation(obs, expected):
    assert _read("kv.resident_weight_bytes", obs) == expected


def test_the_benchmark_lists_the_reader_for_the_chat_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == "kv.resident_weight_bytes")
    assert entry == {"name": "kv.resident_weight_bytes", "unit": "bytes",
                     "better": "lower", "source": "program_counter", "layer": "kv",
                     "moves": "serve_lat_per_tok_p50_ms",
                     "workloads": ["gpt2-large.chat"]}
    assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                       entry["name"] + ".py"))


def test_stats_carries_the_counter_beside_the_block_counters():
    """``/stats`` shows ``block_stats()`` whole (``executor.stats()["blocks"]``):
    the new counter reaches it as ``kv_blocks_read`` does."""
    from deeplearning4j_tpu.monitoring import MetricsRegistry
    from deeplearning4j_tpu.serving import GenerativeInferenceExecutor

    cfg = _cfg()
    pool = PagedDecodeSlotPool(_masters(cfg), cfg, slots=SLOTS, block_T=BLOCK_T)
    ex = GenerativeInferenceExecutor(
        pool, default_max_new_tokens=2, registry=MetricsRegistry())
    try:
        blocks = ex.stats()["blocks"]
    finally:
        ex.stop(drain=False, timeout=10)
    assert blocks["resident_weight_bytes"] == pool.block_stats()[
        "resident_weight_bytes"] > 0
    assert "kv_blocks_read" in blocks
