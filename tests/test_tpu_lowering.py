"""Ahead-of-time compiles for a described TPU v5e, from the CPU: what the
chip's compiler (Mosaic included) would refuse is refused here, at no chip
time, and the compiled program shows whether the paged arenas are updated in
place. Nothing runs, so nothing here is a time or a result.

All such compiles live in THIS file: only one process may hold the TPU
library, the worker that is given this file loads it inside the fixture, and
every other worker merely collects the tests (on-chip-measurement guide,
section 2). The kernels choose interpret mode from ``jax.default_backend()``,
which is the CPU here, so the tests steer that one call onto the chip's path.
"""

import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.kernels.paged_attention import paged_decode_attention
from deeplearning4j_tpu.models import transformer as tfm
from deeplearning4j_tpu.models.paged_decode import PagedDecodeSlotPool

# gpt2-large.chat's widths (benchmark/configs/gpt2-large.json,
# benchmark/traffic/chat.json); the depth is cut to 2 for the test's time
SLOTS, BLOCK_T, MAX_LEN, HEADS, HEAD_DIM, LAYERS = 16, 32, 1024, 20, 64, 2
D_MODEL = HEADS * HEAD_DIM
MAX_BLOCKS = MAX_LEN // BLOCK_T
N_BLOCKS = 1 + SLOTS * MAX_BLOCKS
ARENA = (LAYERS, N_BLOCKS, BLOCK_T, D_MODEL)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_chip_path(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.fixture(scope="module")
def pool_and_params(one_chip):
    """The cell's pool over SHAPES: no parameter and no arena is allocated."""
    cfg = tfm.TransformerConfig(
        vocab_size=50257, max_len=MAX_LEN, d_model=D_MODEL, n_heads=HEADS,
        n_layers=LAYERS, d_ff=4 * D_MODEL, causal=True, dropout=0.0,
        compute_dtype=jnp.bfloat16, norm_position="pre")
    shapes = jax.eval_shape(lambda: tfm.init_params(jax.random.key(0), cfg))
    masters = jax.tree.map(lambda x: _shape(one_chip, x.shape, x.dtype), shapes)
    mp = pytest.MonkeyPatch()
    mp.setattr(PagedDecodeSlotPool, "_new_arena", lambda self, cfg: (None, None))
    try:
        pool = PagedDecodeSlotPool(masters, cfg, slots=SLOTS, block_T=BLOCK_T,
                                   max_len=MAX_LEN)
    finally:
        mp.undo()
    assert pool.n_blocks == N_BLOCKS and pool.max_blocks == MAX_BLOCKS
    # the programs are lowered from what the pool holds: the resident shapes
    # its family made of the float32 masters' shapes
    return pool, pool.params


def _arena_ops(hlo_text):
    """(opcode, shape) of every instruction of the entry computation (what
    the device runs, one kernel each) whose result is shaped like an arena or
    a layer of one, apart from parameters and tuple plumbing."""
    found = []
    entry = hlo_text[hlo_text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    for m in re.finditer(r"= (\w+\[[\d,]+\])\S* ([\w-]+)\(", entry):
        dims = m.group(1)
        if any(s in dims for s in (f"[{LAYERS},{N_BLOCKS},", f"[1,{N_BLOCKS},",
                                   f"[{N_BLOCKS},{BLOCK_T},",
                                   f"[{N_BLOCKS * BLOCK_T},")):
            if m.group(2) not in ("parameter", "get-tuple-element", "bitcast",
                                  "tuple", "custom-call"):
                found.append((m.group(2), dims))
    return found


# float32 tensors shaped like a matmul weight of the family: [D,3D], [D,D],
# [D,4D], [4D,D]; the tied table [V,D] is one too where the HEAD reads it
WEIGHT_SHAPES = [(D_MODEL, 3 * D_MODEL), (D_MODEL, D_MODEL),
                 (D_MODEL, 4 * D_MODEL), (4 * D_MODEL, D_MODEL)]
TABLE_SHAPE = (50257, D_MODEL)


def _f32_weight_parameters(lowered):
    """Shapes of the lowered program's float32 arguments that are shaped like
    a matmul weight (the table counts once: the lookup's float32 copy)."""
    main = lowered.as_text()
    main = main[main.index("func.func public @main("):]
    main = main[:main.index("\n")]
    found = []
    for shape in WEIGHT_SHAPES + [TABLE_SHAPE]:
        found += [shape] * len(re.findall(
            r"tensor<%dx%dxf32>" % shape, main))
    return found


def _assert_in_place(lowered, compiled, n_arenas):
    """Donation survived: the lowered module ties each arena argument to a
    result, the compiled one aliases all their bytes and holds no temporary
    of an arena's size, and no copy of an arena (or of a layer of one) is
    left in it."""
    text = lowered.as_text()
    arena_type = "tensor<" + "x".join(str(d) for d in ARENA) + "xbf16>"
    tied = re.findall(re.escape(arena_type) + r" \{[^%]*?tf\.aliasing_output = (\d+)",
                      text)
    assert len(tied) == len(set(tied)) == n_arenas, tied
    arena_bytes = 2 * LAYERS * N_BLOCKS * BLOCK_T * D_MODEL
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= n_arenas * arena_bytes
    assert mem.temp_size_in_bytes < arena_bytes // LAYERS // 2
    ops = _arena_ops(compiled.as_text())
    assert not [op for op in ops if op[0].startswith("copy")], ops
    return ops


def _step_operands(one_chip, pool):
    """What ``dispatch()`` hands the decode program behind the arenas and the
    tables (ISSUE 38): the step before's output, where a slot's next token
    stays (tokens, then the family's counters), the host's tokens for the
    slots it knows better, and the positions."""
    ints = _shape(one_chip, (pool.slots,), jnp.int32)
    return _shape(one_chip, pool._carry.shape, jnp.int32), ints, ints


def test_decode_program_compiles_for_the_chip_with_arenas_in_place(
        one_chip, on_chip_path, pool_and_params):
    pool, params = pool_and_params
    arena = _shape(one_chip, ARENA, jnp.bfloat16)
    lowered = pool._decode_fn.lower(
        params, arena, arena, _shape(one_chip, (SLOTS, MAX_BLOCKS), jnp.int32),
        *_step_operands(one_chip, pool))
    compiled = lowered.compile()  # a Mosaic error would be raised here
    ops = _assert_in_place(lowered, compiled, n_arenas=2)
    # what XLA does to an arena is one in-place scatter of the window's rows
    # a layer, for K and for V, and nothing else
    assert len(ops) == 2 * LAYERS and all(op == "fusion" for op, _ in ops), ops
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == LAYERS
    assert "paged_decode_attn" in text
    # served from weights cast once: the only float32 argument shaped like a
    # matmul weight is the table the embedding LOOKUP gathers rows of; the
    # compiled step holds no float32 tensor of a block weight's shape and no
    # convert INTO a weight's shape (operands print without their shapes, so
    # a convert of a float32 weight shows as its compute-dtype result)
    assert _f32_weight_parameters(lowered) == [TABLE_SHAPE]
    for shape in WEIGHT_SHAPES:
        assert "f32[%d,%d]" % shape not in text, shape
    for shape in WEIGHT_SHAPES + [TABLE_SHAPE]:
        converts = re.findall(r"= bf16\[%d,%d\]\S* convert\(" % shape, text)
        assert not converts, converts


@pytest.mark.parametrize("bucket", [128, 1024])
def test_prefill_program_compiles_for_the_chip_with_arenas_in_place(
        one_chip, on_chip_path, pool_and_params, bucket):
    pool, params = pool_and_params
    arena = _shape(one_chip, ARENA, jnp.bfloat16)
    lowered = pool._prefill_fn.lower(
        params, arena, arena, _shape(one_chip, (bucket // BLOCK_T,), jnp.int32),
        _shape(one_chip, (1, bucket), jnp.int32), _shape(one_chip, (), jnp.int32))
    _assert_in_place(lowered, lowered.compile(), n_arenas=2)
    assert _f32_weight_parameters(lowered) == [TABLE_SHAPE]


def test_copy_on_write_program_compiles_for_the_chip_in_place(
        one_chip, on_chip_path, pool_and_params):
    pool, _ = pool_and_params
    arena = _shape(one_chip, ARENA, jnp.bfloat16)
    scalar = _shape(one_chip, (), jnp.int32)
    lowered = pool._copy_fn.lower(arena, arena, scalar, scalar)
    _assert_in_place(lowered, lowered.compile(), n_arenas=2)


@pytest.mark.parametrize("W", [1, 5], ids=["W1", "W5"])
def test_paged_attention_kernel_compiles_for_the_chip(one_chip, on_chip_path, W):
    """The verify pass of speculation (W = spec_tokens + 1) has no cell: its
    kernel is compiled here at the cell's widths all the same."""
    fn = jax.jit(lambda q, k, v, tables, limits: paged_decode_attention(
        q, k, v, tables, limits, layer=1, n_heads=HEADS))
    arena = _shape(one_chip, ARENA, jnp.bfloat16)
    compiled = fn.lower(_shape(one_chip, (SLOTS, W, D_MODEL), jnp.bfloat16),
                        arena, arena,
                        _shape(one_chip, (SLOTS, MAX_BLOCKS), jnp.int32),
                        _shape(one_chip, (SLOTS, W), jnp.int32)).compile()
    assert not _arena_ops(compiled.as_text())  # the arenas are read where they lie
    assert "paged_decode_attn" in compiled.as_text()


# -- the kimi_k2 family at Kimi-K2.5's published widths (ISSUE 31) ------------
#
# benchmark/configs/kimi-k2-5.json and benchmark/traffic/agent-decode.json:
# 64 slots x 5120 positions in blocks of 32, 12 resident experts of 384, an
# eighth of the vocabulary; the depth is cut to 1 dense + 1 sparse layer for
# the test's time. A cached row is 576 values in 640 lanes (five whole
# 128-lane tiles: the chip's DMA engine moves no half tile).

K2_SLOTS, K2_MAX_LEN, K2_LAYERS, K2_LANES = 64, 5120, 2, 640
K2_MAX_BLOCKS = K2_MAX_LEN // BLOCK_T
K2_N_BLOCKS = 1 + K2_SLOTS * K2_MAX_BLOCKS
K2_ARENA = (K2_LAYERS, K2_N_BLOCKS, BLOCK_T, K2_LANES)


@pytest.fixture(scope="module")
def latent_pool_and_params(one_chip):
    from deeplearning4j_tpu.models import kimi_k2 as k2

    cfg = k2.KimiK2Config(vocab_size=20480, num_hidden_layers=K2_LAYERS,
                          n_resident_experts=12, max_position_embeddings=K2_MAX_LEN)
    assert cfg.cache_width == 576 and cfg.arena_width == K2_LANES
    shapes = jax.eval_shape(lambda: k2.init_params(jax.random.key(0), cfg))
    params = jax.tree.map(lambda x: _shape(one_chip, x.shape, x.dtype), shapes)
    mp = pytest.MonkeyPatch()
    mp.setattr(PagedDecodeSlotPool, "_new_arena", lambda self, cfg: (None,))
    try:
        pool = PagedDecodeSlotPool(shapes, cfg, slots=K2_SLOTS, block_T=BLOCK_T,
                                   max_len=K2_MAX_LEN)
    finally:
        mp.undo()
    assert pool.n_blocks == K2_N_BLOCKS and pool.max_blocks == K2_MAX_BLOCKS
    return pool, params


def _assert_latent_arena_in_place(lowered, compiled):
    arena_type = "tensor<" + "x".join(str(d) for d in K2_ARENA) + "xbf16>"
    tied = re.findall(re.escape(arena_type) + r" \{[^%]*?tf\.aliasing_output = (\d+)",
                      lowered.as_text())
    assert len(tied) == 1, tied
    arena_bytes = 2 * K2_LAYERS * K2_N_BLOCKS * BLOCK_T * K2_LANES
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= arena_bytes
    # nothing of an arena's size is made beside it: a layer of it is 0.42 GB
    assert mem.temp_size_in_bytes < arena_bytes // K2_LAYERS
    entry = compiled.as_text()
    entry = entry[entry.index("\nENTRY "):]
    copies = re.findall(r"= bf16\[(?:%d,)?%d,%d,%d\]\S* copy\(" % (
        K2_LAYERS, K2_N_BLOCKS, BLOCK_T, K2_LANES), entry)
    assert not copies, copies


def test_latent_decode_program_compiles_for_the_chip_with_the_arena_in_place(
        one_chip, on_chip_path, latent_pool_and_params):
    pool, params = latent_pool_and_params
    lowered = pool._decode_fn.lower(
        params, _shape(one_chip, K2_ARENA, jnp.bfloat16),
        _shape(one_chip, (K2_SLOTS, K2_MAX_BLOCKS), jnp.int32),
        *_step_operands(one_chip, pool))
    compiled = lowered.compile()  # a Mosaic error would be raised here
    _assert_latent_arena_in_place(lowered, compiled)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == K2_LAYERS
    assert "paged_mla_decode_attn" in text


def test_latent_prefill_program_compiles_for_the_chip_with_the_arena_in_place(
        one_chip, on_chip_path, latent_pool_and_params):
    """The 1024 bucket (the cell's median prompt): flash attention with
    192-wide q and k, the expert rows in tiles, the rows stored in place."""
    pool, params = latent_pool_and_params
    lowered = pool._prefill_fn.lower(
        params, _shape(one_chip, K2_ARENA, jnp.bfloat16),
        _shape(one_chip, (1024 // BLOCK_T,), jnp.int32),
        _shape(one_chip, (1, 1024), jnp.int32), _shape(one_chip, (), jnp.int32))
    compiled = lowered.compile()
    _assert_latent_arena_in_place(lowered, compiled)
    assert "flash_fwd" in compiled.as_text()


# -- the flash kernels with the blocks the static table answers (ISSUE 32) -----
#
# the training cells' attention shapes (benchmark/traffic/mlm-t512.json: 16
# sequences x 16 heads x 512 with key padding; train-dp2tp2.json: 4 x 10 heads
# a chip x 1024, causal) through ``jax.grad``, and kimi's prefill call with
# its explicit 1024 block: Mosaic accepts the blocks and a grid step fits VMEM


@pytest.mark.parametrize("shape,causal,masked", [
    ((16, 16, 512, 64), False, True),
    ((4, 10, 1024, 64), True, False),
], ids=["bert_t512_masked", "gpt2_t1024_causal"])
def test_flash_grad_compiles_for_the_chip_with_the_tables_blocks(
        one_chip, on_chip_path, shape, causal, masked):
    from deeplearning4j_tpu.kernels import flash_attention
    from deeplearning4j_tpu.kernels.autotune import FLASH_KERNELS, static_flash_blocks

    B, H, T, D = shape

    def loss(q, k, v, mask):
        out = flash_attention(q, k, v, mask if masked else None, causal=causal)
        return jnp.sum(out.astype(jnp.float32))

    qkv = _shape(one_chip, shape, jnp.bfloat16)
    mask = _shape(one_chip, (B, T), jnp.float32)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        qkv, qkv, qkv, mask).compile()  # a Mosaic error would be raised here
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for name in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
        assert name in text
    # the kernels write what benchmark/work.py:classify_flash_call reads
    assert f"(bf16[{B * H},{T},{D}]" in text and f"f32[{B * H},{T},1]" in text
    # and the grids are the table's: one step a head where a block is whole
    blocks = [static_flash_blocks(T, T, D=D, causal=causal, kernel=kn)
              for kn in FLASH_KERNELS]
    assert blocks == ([(512, 512)] * 3 if masked
                      else [(1024, 1024), (512, 512), (512, 512)])


def test_flash_forward_compiles_for_the_chip_at_kimis_prefill_block(
        one_chip, on_chip_path):
    """models/kimi_k2.py asks for 1024 x 1024 at its call site (q and k 192
    wide, v padded to them): an explicit block wins over the table."""
    from deeplearning4j_tpu.kernels import flash_attention

    qkv = _shape(one_chip, (1, 64, 2048, 192), jnp.bfloat16)
    compiled = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, scale=0.1, block_q=1024, block_k=1024)).lower(
            qkv, qkv, qkv).compile()
    assert "flash_fwd" in compiled.as_text()


# -- the keye_vl family at Keye-VL-2.0-30B-A3B's published widths (ISSUE 35) ---
#
# benchmark/configs/keye-vl-2-30b-a3b.json and benchmark/traffic/longdoc-qa.json:
# 16 slots x 17,408 positions in blocks of 32, THREE arenas (K and V of 4 x 128
# lanes, the index key's 64 values in 128 lanes), all 128 experts stacked, the
# whole vocabulary; the depth is cut to 2 for the test's time.

KV_SLOTS, KV_MAX_LEN, KV_LAYERS = 16, 17408, 2
KV_MAX_BLOCKS = KV_MAX_LEN // BLOCK_T
KV_N_BLOCKS = 1 + KV_SLOTS * KV_MAX_BLOCKS
KV_ARENAS = [(KV_LAYERS, KV_N_BLOCKS, BLOCK_T, w) for w in (512, 512, 128)]


@pytest.fixture(scope="module")
def sparse_pool_and_params(one_chip):
    from deeplearning4j_tpu.models import keye_vl as kv

    cfg = kv.KeyeVLConfig(num_hidden_layers=KV_LAYERS, max_position_embeddings=KV_MAX_LEN)
    shapes = jax.eval_shape(lambda: kv.init_params(jax.random.key(0), cfg))
    params = jax.tree.map(lambda x: _shape(one_chip, x.shape, x.dtype), shapes)
    mp = pytest.MonkeyPatch()
    mp.setattr(PagedDecodeSlotPool, "_new_arena", lambda self, cfg: (None,) * 3)
    try:
        pool = PagedDecodeSlotPool(shapes, cfg, slots=KV_SLOTS, block_T=BLOCK_T,
                                   max_len=KV_MAX_LEN)
    finally:
        mp.undo()
    assert pool.n_blocks == KV_N_BLOCKS and pool.family.cache_widths == (512, 512, 128)
    return pool, params


def _assert_three_arenas_in_place(lowered, compiled):
    text = lowered.as_text()
    tied = []
    for arena in KV_ARENAS[1:]:   # K and V share a type
        arena_type = "tensor<" + "x".join(str(d) for d in arena) + "xbf16>"
        tied += re.findall(re.escape(arena_type) + r" \{[^%]*?tf\.aliasing_output = (\d+)",
                           text)
    assert len(tied) == len(set(tied)) == 3, tied
    arena_bytes = [2 * KV_LAYERS * KV_N_BLOCKS * BLOCK_T * w for w in (512, 512, 128)]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(arena_bytes)
    entry = compiled.as_text()
    entry = entry[entry.index("\nENTRY "):]
    copies = re.findall(r"= bf16\[(?:%d,)?%d,%d,(?:512|128)\]\S* copy\(" % (
        KV_LAYERS, KV_N_BLOCKS, BLOCK_T), entry)
    assert not copies, copies
    return mem


def test_sparse_decode_program_compiles_for_the_chip_with_three_arenas_in_place(
        one_chip, on_chip_path, sparse_pool_and_params):
    pool, params = sparse_pool_and_params
    lowered = pool._decode_fn.lower(
        params, *(_shape(one_chip, a, jnp.bfloat16) for a in KV_ARENAS),
        _shape(one_chip, (KV_SLOTS, KV_MAX_BLOCKS), jnp.int32),
        *_step_operands(one_chip, pool))
    compiled = lowered.compile()
    mem = _assert_three_arenas_in_place(lowered, compiled)
    # nothing of a K arena's layer is made beside it: a step gathers the
    # index keys of the mapped blocks (71 MB a layer) and 2,048 rows a slot
    assert mem.temp_size_in_bytes < 2 * KV_N_BLOCKS * BLOCK_T * 512
    text = compiled.as_text()
    # ONE loop a layer runs the trips of all 128 experts
    assert len(re.findall(r"\n\s*\S+ = \([^\n]*f32\[16,2048\][^\n]* while\(",
                          text)) == KV_LAYERS
    # the top-2048 is no sort of [slots, max_len] (ISSUE 40) but ONE Mosaic
    # kernel a layer, over the live slots
    assert not re.findall(r"\[%d,%d\][^\n]* sort\(" % (KV_SLOTS, KV_MAX_LEN), text)
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sum("dsa_decode_select" in line for line in calls) == KV_LAYERS, len(calls)


def test_sparse_prefill_program_compiles_for_the_chip_with_three_arenas_in_place(
        one_chip, on_chip_path, sparse_pool_and_params):
    """The 8,192 bucket (the cell's median prompt): the exact k-th score and
    the attention under the selection's mask are Mosaic kernels, one each in
    the loop over query chunks of a layer."""
    pool, params = sparse_pool_and_params
    lowered = pool._prefill_fn.lower(
        params, *(_shape(one_chip, a, jnp.bfloat16) for a in KV_ARENAS),
        _shape(one_chip, (8192 // BLOCK_T,), jnp.int32),
        _shape(one_chip, (1, 8192), jnp.int32), _shape(one_chip, (), jnp.int32))
    compiled = lowered.compile()  # a Mosaic error would be raised here
    _assert_three_arenas_in_place(lowered, compiled)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2 * KV_LAYERS
    assert "dsa_kth_score" in text and "dsa_selected_attn" in text


# -- the trinity family at Trinity-Large-Preview's published widths (ISSUE 37) --
#
# benchmark/configs/trinity-large-preview.json and benchmark/traffic/mixed-len.json:
# 16 slots x 33,792 positions in blocks of 32, TWO cache groups (the full layers'
# K and V for the request's life, the sliding layers' within the 4,096 window:
# 130 blocks a slot), 48 query heads over 8 K/V heads of 128 lanes, 32 of 256
# experts stacked, an eighth of the vocabulary. The depth is cut to 2 for the
# test's time: a dense sliding layer and a full layer with experts.

TR_SLOTS, TR_MAX_LEN, TR_WINDOW = 16, 33792, 4096
TR_MAX_BLOCKS = TR_MAX_LEN // BLOCK_T
TR_N_BLOCKS = (1 + TR_SLOTS * TR_MAX_BLOCKS, 1 + TR_SLOTS * (TR_WINDOW // BLOCK_T + 2))
TR_ARENAS = [(1, n, BLOCK_T, 1024) for n in TR_N_BLOCKS for _ in range(2)]


@pytest.fixture(scope="module")
def windowed_pool_and_params(one_chip):
    from deeplearning4j_tpu.models import trinity as tr

    cfg = tr.TrinityConfig(
        vocab_size=25024, num_hidden_layers=2, num_dense_layers=1,
        layer_types=(tr.SLIDING, tr.FULL), n_resident_experts=32,
        max_position_embeddings=TR_MAX_LEN)
    shapes = jax.eval_shape(lambda: tr.init_params(jax.random.key(0), cfg))
    params = jax.tree.map(lambda x: _shape(one_chip, x.shape, x.dtype), shapes)
    mp = pytest.MonkeyPatch()
    mp.setattr(PagedDecodeSlotPool, "_new_arena", lambda self, cfg: (None,) * 4)
    try:
        pool = PagedDecodeSlotPool(shapes, cfg, slots=TR_SLOTS, block_T=BLOCK_T,
                                   max_len=TR_MAX_LEN)
    finally:
        mp.undo()
    assert pool.n_blocks == TR_N_BLOCKS and pool.family.cache_widths == (1024,) * 4
    assert pool.family.arena_groups == (0, 0, 1, 1)
    return pool, params


def _assert_four_arenas_in_place(lowered, compiled):
    text = lowered.as_text()
    tied = []
    for arena in TR_ARENAS[::2]:   # K and V of a group share a type
        arena_type = "tensor<" + "x".join(str(d) for d in arena) + "xbf16>"
        tied += re.findall(re.escape(arena_type) + r" \{[^%]*?tf\.aliasing_output = (\d+)",
                           text)
    assert len(tied) == len(set(tied)) == 4, tied
    arena_bytes = sum(2 * n * BLOCK_T * 1024 for _, n, _, _ in TR_ARENAS)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= arena_bytes
    entry = compiled.as_text()
    entry = entry[entry.index("\nENTRY "):]
    copies = re.findall(r"= bf16\[(?:1,)?(?:%d|%d),%d,1024\]\S* copy\(" % (
        *TR_N_BLOCKS, BLOCK_T), entry)
    assert not copies, copies
    return mem


def test_windowed_decode_program_compiles_for_the_chip_with_four_arenas_in_place(
        one_chip, on_chip_path, windowed_pool_and_params):
    """Two tables, the grouped decode kernel twice (``starts`` on the sliding
    layer: nine scalar lists, seven on the full one), the arenas of both
    cache groups updated in place."""
    pool, params = windowed_pool_and_params
    table = _shape(one_chip, (TR_SLOTS, TR_MAX_BLOCKS), jnp.int32)
    lowered = pool._decode_fn.lower(
        params, *(_shape(one_chip, a, jnp.bfloat16) for a in TR_ARENAS), table, table,
        *_step_operands(one_chip, pool))
    compiled = lowered.compile()  # a Mosaic error would be raised here
    mem = _assert_four_arenas_in_place(lowered, compiled)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "paged_decode_attn" in text
    # nothing the size of a full layer's arena is made beside it
    assert mem.temp_size_in_bytes < 2 * TR_N_BLOCKS[0] * BLOCK_T * 1024


@pytest.mark.parametrize("bucket", [2048, 32768])
def test_windowed_prefill_program_compiles_for_the_chip_with_four_arenas_in_place(
        one_chip, on_chip_path, windowed_pool_and_params, bucket):
    """A bucket under the window and the longest: the sliding layer through
    ``flash_fwd_swa``, the full one through ``flash_fwd_gqa``, rows in passes
    of ``prefill_chunk``; a 32k bucket's temporaries stay near 2 GB."""
    pool, params = windowed_pool_and_params
    dest = _shape(one_chip, (bucket // BLOCK_T,), jnp.int32)
    lowered = pool._prefill_fn.lower(
        params, *(_shape(one_chip, a, jnp.bfloat16) for a in TR_ARENAS), dest, dest,
        _shape(one_chip, (1, bucket), jnp.int32), _shape(one_chip, (), jnp.int32))
    compiled = lowered.compile()  # a Mosaic error would be raised here
    mem = _assert_four_arenas_in_place(lowered, compiled)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "flash_fwd_swa" in text and "flash_fwd_gqa" in text
    # the kernels write what benchmark/work_trinity.py:call_bucket reads
    assert f"bf16[1,{bucket},6144]" in text   # [batch, T, heads x head_dim]
    assert mem.temp_size_in_bytes < 2.5e9


def test_equal_heads_decode_kernel_lowers_as_it_did(one_chip, on_chip_path):
    """``kv_heads == n_heads`` and no ``starts``: seven scalar lists and the
    [slots, H * hd] query block, as gpt2-large.chat's step has run them since
    ISSUE 29; the grouped, windowed call has nine and a head a row."""
    def text(**kw):
        H, G = kw.pop("heads")
        q = _shape(one_chip, (SLOTS, 1, H * 128), jnp.bfloat16)
        arena = _shape(one_chip, (2, N_BLOCKS, BLOCK_T, G * 128), jnp.bfloat16)
        ints = _shape(one_chip, (SLOTS, 1), jnp.int32)
        tables = _shape(one_chip, (SLOTS, MAX_BLOCKS), jnp.int32)
        if kw.pop("windowed"):
            fn = lambda q, k, v, t, l, s: paged_decode_attention(  # noqa: E731
                q, k, v, t, l, layer=1, n_heads=H, kv_heads=G, starts=s)
            return jax.jit(fn).lower(q, arena, arena, tables, ints, ints).compile().as_text()
        fn = lambda q, k, v, t, l: paged_decode_attention(  # noqa: E731
            q, k, v, t, l, layer=1, n_heads=H)
        return jax.jit(fn).lower(q, arena, arena, tables, ints).compile().as_text()

    plain = text(heads=(10, 10), windowed=False)
    grouped = text(heads=(48, 8), windowed=True)
    for t in (plain, grouped):
        assert t.count('custom_call_target="tpu_custom_call"') == 1
    assert f"f32[{SLOTS},1280]" in plain and f"f32[{SLOTS * 48},128]" in grouped


# -- the qkv projection: split by heads in the weight where a mesh splits
# heads (the four-chip cell), today's one matmul and split where none does

_EXCHANGE = re.compile(r"= (\(?[a-z]\w*\[.*?) "
                       r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
                       r"collective-permute)(?:-start)?\(.*?channel_id=(\d+)")


def _exchanges(hlo_text):
    """{channel_id: (opcode, result shapes)}: a collective once, however many
    fusion clones the text repeats it in."""
    found = {}
    for line in hlo_text.splitlines():
        m = _EXCHANGE.search(line)
        if m:
            found.setdefault(m.group(3), (m.group(2),
                                          re.findall(r"\w+\[[\d,]*\]", m.group(1))))
    return found


def test_the_four_chip_step_moves_qkv_w_and_not_its_activations(topo, on_chip_path):
    """gpt2-large.train-dp2tp2's step (benchmark/runners/train.py's placement:
    data 2 x tp 2, B 4 a replica, T 1024, the published widths, depth cut to
    1) for a described v5e:2x2. Flash runs per shard with heads over tp; the
    projection hands it those heads, so no all-to-all and no
    collective-permute is left, and what crosses for q, k and v is ``qkv_w``
    (bf16 forward, its gradient back) and its bias."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.parallel.partition import Partitioner, SpecLayout

    cfg = tfm.TransformerConfig(
        vocab_size=50257, max_len=1024, d_model=1280, n_heads=20, n_layers=1,
        d_ff=5120, causal=True, dropout=0.0, norm_position="pre")
    updater = Adam(1e-4)
    layout = SpecLayout(data=2, fsdp=1, tp=2)
    part = Partitioner(layout, mesh=layout.build_mesh(list(topo.devices)))
    p_shapes = jax.eval_shape(lambda: tfm.init_params(jax.random.key(0), cfg))
    s_shapes = jax.eval_shape(updater.init, p_shapes)
    p_specs = part.spec_tree(p_shapes)
    s_specs = Partitioner.state_spec_tree(s_shapes, p_specs)
    keep = jax.tree.map(part.sharding_for, (p_specs, s_specs),
                        is_leaf=lambda x: isinstance(x, P))
    placed = lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)  # noqa: E731
    rows = NamedSharding(part.mesh, P("data"))
    whole = NamedSharding(part.mesh, P())
    batch = {k: jax.ShapeDtypeStruct((8, 1024), dt, sharding=rows) for k, dt in
             (("tokens", jnp.int32), ("labels", jnp.int32), ("weights", jnp.float32))}
    args = (jax.tree.map(placed, p_shapes, keep[0]),
            jax.tree.map(placed, s_shapes, keep[1]), batch,
            jax.ShapeDtypeStruct((), jnp.int32, sharding=whole),
            jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=whole))
    traced = tfm.head_major_blocks
    with jax.sharding.set_mesh(part.mesh):
        step = jax.jit(tfm.make_train_step(cfg, updater), donate_argnums=(0, 1),
                       out_shardings=(*keep, None))
        text = step.lower(*args).compile().as_text()
    assert tfm.head_major_blocks - traced == 1
    found = _exchanges(text)
    assert [f for f in found.values()
            if f[0] in ("all-to-all", "collective-permute")] == []
    moved = sorted(sh[0] for op, sh in found.values() if op == "all-gather"
                   and "1024" not in sh[0])
    assert moved == ["bf16[1280,3,20,64]", "bf16[1280,3840]",
                     "bf16[2,1,1920]", "bf16[3,20,64]"], moved
    for name in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
        assert name in text


def test_a_bert_block_compiles_for_one_chip_as_before(one_chip, on_chip_path):
    """bert-large.mlm-t512's block, forward and backward, on one described
    chip: no mesh, so the projection is one matmul and a split, with no
    head-major view of ``qkv_w`` and no collective, and flash is the kernel."""
    cfg = tfm.TransformerConfig.bert_large(
        max_len=512, n_layers=1, dropout=0.0, norm_position="post",
        gelu_approximate=False)
    block = jax.eval_shape(lambda: tfm.init_params(jax.random.key(0), cfg))["blocks"][0]
    block = jax.tree.map(lambda x: _shape(one_chip, x.shape, x.dtype), block)
    h = _shape(one_chip, (16, 512, 1024), jnp.bfloat16)
    mask = _shape(one_chip, (16, 512), jnp.float32)

    def loss(p, h, mask):
        return jnp.sum(tfm._block(cfg, p, h, mask, None, False).astype(jnp.float32))

    traced = tfm.head_major_blocks
    lowered = jax.jit(jax.grad(loss)).lower(block, h, mask)
    assert tfm.head_major_blocks == traced
    assert "[1024,3,16,64]" not in lowered.as_text()
    text = lowered.compile().as_text()
    assert _exchanges(text) == {}
    for name in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
        assert name in text
