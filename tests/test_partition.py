"""Sharded-parameter training tests (ISSUE 9): SpecLayout role→spec policy,
fsdp×tp loss parity with the replicated gang, per-device shard accounting,
layout-aware checkpoints, the bundled-model coverage gate, and the donation
lint for fused-step compilations.

The multi-process acceptance tier (per-rank byte shrink over a real gang,
sharded-checkpoint round trip across gangs) rides tests/mp_workers.py in
test_multiprocess.py (slow-marked)."""

import ast
import pathlib
import re

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.nn import (ComputationGraph, MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf import (BatchNormalization, DenseLayer,
                                        EmbeddingSequenceLayer, GravesLSTM,
                                        InputType, LSTM, OutputLayer,
                                        RnnOutputLayer)
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.parallel import (ParallelTrainer, Partitioner,
                                         SpecLayout, build_mesh,
                                         param_role_tree)
from deeplearning4j_tpu.parallel.partition import uncovered_params
from deeplearning4j_tpu.parallel.sharding import batch_sharding

ROOT = pathlib.Path(__file__).resolve().parent.parent / "deeplearning4j_tpu"


def _mlp(seed=7, classes=4, hidden=16):
    conf = (NeuralNetConfiguration.Builder().seed(seed).updater(Adam(1e-2)).list()
            .layer(DenseLayer(n_in=8, n_out=hidden, activation="tanh"))
            .layer(BatchNormalization())
            .layer(DenseLayer(n_in=hidden, n_out=hidden, activation="relu"))
            .layer(OutputLayer(n_out=classes, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(8))
            .build())
    return MultiLayerNetwork(conf).init()


def _batches(steps=10, n=16, classes=4):
    out = []
    for s in range(steps):
        rs = np.random.RandomState(100 + s)
        x = rs.rand(n, 8).astype(np.float32)
        y = np.eye(classes, dtype=np.float32)[rs.randint(0, classes, n)]
        out.append(DataSet(x, y))
    return out


# ------------------------------------------------------------ role → spec map


def test_spec_layout_assigns_specs_by_role():
    net = _mlp()
    layout = SpecLayout(data=2, fsdp=2, tp=2)
    part = Partitioner(layout)
    specs = part.spec_tree(net.params_, param_role_tree(net))
    assert specs["0"]["W"] == P("fsdp", "tp")     # dense kernel
    assert specs["0"]["b"] == P("fsdp")           # bias over fsdp
    assert specs["1"]["gamma"] == P("fsdp")       # norm over fsdp
    assert specs["3"]["W"] == P("fsdp", "tp")


def test_spec_layout_embedding_table_shards_vocab_over_fsdp_x_tp():
    conf = (NeuralNetConfiguration.Builder().seed(3).updater(Adam(1e-2)).list()
            .layer(EmbeddingSequenceLayer(n_in=64, n_out=8))
            .layer(RnnOutputLayer(n_in=8, n_out=4, activation="softmax",
                                  loss="mcxent"))
            .set_input_type(InputType.recurrent(64, 5))
            .build())
    net = MultiLayerNetwork(conf).init()
    part = Partitioner(SpecLayout(data=2, fsdp=2, tp=2))
    specs = part.spec_tree(net.params_, param_role_tree(net))
    # the [vocab, dim] table: vocab dim over fsdp AND tp combined
    assert specs["0"]["W"] == P(("fsdp", "tp"))


def test_divisibility_fallback_is_per_axis_and_reported():
    net = _mlp(classes=3)  # 3-class head: 3 divides neither fsdp=2 nor tp=2
    part = Partitioner(SpecLayout(data=2, fsdp=2, tp=2))
    rep: dict = {}
    specs = part.spec_tree(net.params_, param_role_tree(net), report=rep)
    # kernel [16, 3]: dim0 keeps fsdp, dim1 drops tp
    assert specs["3"]["W"] == P("fsdp")
    # bias [3]: nothing divides → replicated AND reported, never silent
    assert specs["3"]["b"] == P()
    assert "3/b" in rep["replicated_fallback"]
    assert rep["uncovered"] == []


# ------------------------------------------- the tensor-parallel pairing


def _transformer_shapes(**kw):
    """The functional transformer's tree as shapes (a spec needs no more)."""
    from deeplearning4j_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig.tiny(**kw)
    return cfg, jax.eval_shape(lambda: tfm.init_params(jax.random.key(0), cfg))


def _dp2tp2():
    """The four-chip cell's layout on four of the suite's host devices."""
    layout = SpecLayout(data=2, fsdp=1, tp=2)
    return Partitioner(layout, mesh=layout.build_mesh(jax.devices()[:4]))


def _axes(spec, dim):
    """The mesh axes a spec puts on one dim, as a tuple."""
    ax = tuple(spec)[dim] if dim < len(tuple(spec)) else None
    return () if ax is None else ax if isinstance(ax, tuple) else (ax,)


def _over_tp(spec):
    return [d for d in range(len(tuple(spec))) if "tp" in _axes(spec, d)]


# leaf of a block -> the dims tp takes under the pairing (Megatron's): the
# first matrix of a pair by columns with its bias, the second by rows, and
# nothing else of a block at all
_BLOCK_TP_DIMS = {
    "qkv_w": [1], "qkv_b": [0], "out_w": [0], "out_b": [],
    "ffn_w1": [1], "ffn_b1": [0], "ffn_w2": [0], "ffn_b2": [],
    "ln1_scale": [], "ln1_bias": [], "ln2_scale": [], "ln2_bias": [],
}


@pytest.mark.parametrize("leaf", sorted(_BLOCK_TP_DIMS))
def test_transformer_block_pairs_its_tp_splits(leaf):
    """ISSUE 36: which dim of a kernel goes over tp follows from the side of
    the pair it stands on, so the activation between the two stays split."""
    _, shapes = _transformer_shapes()
    specs = _dp2tp2().spec_tree(shapes)
    for block in specs["blocks"]:
        assert _over_tp(block[leaf]) == _BLOCK_TP_DIMS[leaf], block[leaf]


def test_transformer_head_and_norms_are_whole_over_tp():
    """The head's ``mlm/w`` is classified by its PATH (OCNN's ``w`` stays a
    plain kernel): its input is the whole residual stream and its output is
    normalised whole, so splitting it would only buy a gather."""
    from deeplearning4j_tpu.nn.conf import (ROLE_KERNEL, ROLE_KERNEL_WHOLE,
                                            classify_param_tree)

    _, shapes = _transformer_shapes(vocab_size=1001)   # odd, as GPT-2's
    roles = classify_param_tree(shapes)
    assert roles["mlm"]["w"] == ROLE_KERNEL_WHOLE
    assert classify_param_tree({"w": np.zeros((4, 4))})["w"] == ROLE_KERNEL
    specs = _dp2tp2().spec_tree(shapes)
    for name, spec in specs["mlm"].items():
        assert _over_tp(spec) == [], (name, spec)
    # an odd vocabulary divides nothing: the tied table is whole too
    assert _over_tp(specs["embed"]["tok"]) == []
    for name in ("ln_scale", "ln_bias"):
        assert _over_tp(specs["embed"][name]) == []


def test_fsdp_keeps_the_dim_tp_does_not_take():
    _, shapes = _transformer_shapes()
    specs = Partitioner(SpecLayout(data=2, fsdp=2, tp=2)).spec_tree(shapes)
    block = specs["blocks"][0]
    assert block["qkv_w"] == P("fsdp", "tp") == block["ffn_w1"]
    assert block["out_w"] == P("tp", "fsdp") == block["ffn_w2"]
    assert block["qkv_b"] == P(("tp", "fsdp")) == block["ffn_b1"]
    assert block["out_b"] == P("fsdp") == block["ln1_scale"]
    assert specs["mlm"]["w"] == P("fsdp")
    # an nn/ Dense kernel is no side of a tagged pair: today's spec
    dense = Partitioner(SpecLayout(data=2, fsdp=2, tp=2)).spec_tree(
        {"0": {"W": np.zeros((8, 16), np.float32)}})
    assert dense["0"]["W"] == P("fsdp", "tp")


def test_the_role_policy_is_the_only_statement_of_the_transformers_layout():
    """``models.transformer.partition_specs`` said the pairing while no cell
    ran it (ISSUE 36); its callers place through ``Partitioner.spec_tree``."""
    from deeplearning4j_tpu import models
    from deeplearning4j_tpu.models import transformer as tfm

    assert not hasattr(tfm, "partition_specs")
    assert not hasattr(models, "transformer_partition_specs")
    # and the layout identity a checkpoint records knows axes only
    assert SpecLayout(data=2, fsdp=1, tp=2).describe() == {
        "axes": {"data": 2, "fsdp": 1, "tp": 2},
        "axis_names": ["data", "fsdp", "tp"]}


# -- the compiled step's census of collectives (what says the layout engaged)

_CENSUS = dict(B=4, T=32, D=64, F=256, V=1001, L=2)
_COLLECTIVE = re.compile(
    r"= (\(?[a-z]\w*\[.*?) "
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")


def _collectives(hlo_text):
    """[(opcode, dims)] for every array a collective of the compiled module
    returns (a combined collective returns a tuple: one entry an array)."""
    out = []
    for line in hlo_text.splitlines():
        m = _COLLECTIVE.search(line)
        if m:
            out += [(m.group(2), tuple(int(d) for d in dims.split(",") if d))
                    for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(1))]
    return out


@pytest.fixture(scope="module")
def dp2tp2_step():
    """A 2-layer causal transformer with an odd vocabulary under data 2 x
    tp 2, placed by the four-chip cell's own sequence (``spec_tree`` ->
    ``state_spec_tree`` -> ``out_shardings``: benchmark/runners/train.py):
    the compiled step's collectives, its first loss, the loss of the same
    step on one device, and the blocks its trace viewed ``qkv_w`` head-major."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import transformer as tfm

    c = _CENSUS
    cfg = tfm.TransformerConfig(
        vocab_size=c["V"], max_len=c["T"], d_model=c["D"], n_heads=4,
        n_layers=c["L"], d_ff=c["F"], causal=True, dropout=0.0,
        attn_impl="xla", compute_dtype=jnp.float32)
    updater = Adam(1e-4)
    part = _dp2tp2()
    init = lambda key: tfm.init_params(key, cfg)  # noqa: E731
    p_shapes = jax.eval_shape(init, jax.random.key(0))
    p_specs = part.spec_tree(p_shapes)
    s_specs = Partitioner.state_spec_tree(
        jax.eval_shape(updater.init, p_shapes), p_specs)
    keep = jax.tree.map(part.sharding_for, (p_specs, s_specs),
                        is_leaf=lambda x: isinstance(x, P))
    rs = np.random.RandomState(0)
    tokens = rs.randint(0, c["V"], (c["B"], c["T"])).astype(np.int32)
    batch = {"tokens": tokens, "labels": np.roll(tokens, -1, axis=1),
             "weights": np.ones(tokens.shape, np.float32)}
    args = (jnp.asarray(0, jnp.int32), jax.random.key(1))
    with jax.sharding.set_mesh(part.mesh):
        params = jax.jit(init, out_shardings=keep[0])(jax.random.key(3))  # donate-ok: makes the state
        opt = jax.jit(updater.init, out_shardings=keep[1])(params)  # donate-ok: makes the state
        whole = jax.tree.map(np.asarray, params)
        step = jax.jit(tfm.make_train_step(cfg, updater), donate_argnums=(0, 1),
                       out_shardings=(*keep, None))
        placed = jax.device_put(batch, batch_sharding(part.mesh))
        traced = tfm.head_major_blocks
        compiled = step.lower(params, opt, placed, *args).compile()
        head_major = tfm.head_major_blocks - traced
        loss = float(compiled(params, opt, placed, *args)[2])
    one = jax.jit(tfm.make_train_step(cfg, updater), donate_argnums=(0, 1))
    loss_one = float(one(whole, updater.init(whole), batch, *args)[2])
    return _collectives(compiled.as_text()), loss, loss_one, head_major


def _dims_end(dims, *tail):
    return dims[-len(tail):] == tail


@pytest.mark.parametrize("case", ["no_gather_of_the_hidden",
                                  "no_sum_of_the_logits",
                                  "no_gather_of_the_table",
                                  "four_sums_a_block_at_most",
                                  "same_first_loss"])
def test_dp2tp2_step_census(dp2tp2_step, case):
    """ISSUE 36: with the splits paired no matmul of a block contracts over a
    dim that is split while its input is not, so the activation between the
    two matrices of a pair is never gathered, the head sums no logits, the
    tied table's gradient is never gathered back, and a block's only
    ``[B,T,D]`` sums are its two pairs', forward and backward."""
    found, loss, loss_one, _ = dp2tp2_step
    c = _CENSUS
    assert found, "the census parsed no collective at all"
    of = lambda op, *tail: [d for o, d in found  # noqa: E731
                            if o == op and _dims_end(d, *tail)]
    if case == "no_gather_of_the_hidden":
        assert of("all-gather", c["T"], c["F"]) == []
    elif case == "no_sum_of_the_logits":
        assert of("all-reduce", c["T"], c["V"]) == []
    elif case == "no_gather_of_the_table":
        assert of("all-gather", c["V"], c["D"]) == []
    elif case == "four_sums_a_block_at_most":
        assert len(of("all-reduce", c["T"], c["D"])) <= 4 * c["L"]
    else:
        assert abs(loss - loss_one) <= 1e-5 * abs(loss_one), (loss, loss_one)


@pytest.mark.parametrize("case", ["no_exchange_of_the_activations",
                                  "qkv_w_moves_once_a_block_and_direction",
                                  "every_block_viewed_head_major"])
def test_dp2tp2_step_splits_qkv_by_heads_in_the_weight(dp2tp2_step, case):
    """``qkv_w``'s columns are stored q, then k, then v and split
    contiguously over tp, while attention wants each device's own heads of
    all three. The projection contracts with a head-major view of the weight
    pinned over tp, so no activation crosses chips for q, k or v, forward or
    backward: what crosses is ``qkv_w`` itself (its bf16 cast going forward,
    its gradient coming back), once a block and direction."""
    found, _, _, head_major = dp2tp2_step
    c = _CENSUS
    if case == "no_exchange_of_the_activations":
        assert [(o, d) for o, d in found
                if o in ("all-to-all", "collective-permute") and c["T"] in d] == []
    elif case == "qkv_w_moves_once_a_block_and_direction":
        moved = [d for o, d in found
                 if o != "all-reduce" and int(np.prod(d)) == 3 * c["D"] ** 2]
        assert len(moved) == 2 * c["L"], moved
    else:
        assert head_major == c["L"]


def _traced_block(mesh, *, d_model=64, n_heads=4):
    """One block over whole sequences traced under ``mesh`` (None: no mesh):
    its jaxpr, the head-major view's shape, and the blocks viewed so."""
    import contextlib

    import jax.numpy as jnp

    from deeplearning4j_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=97, max_len=16, d_model=d_model,
                                n_heads=n_heads, n_layers=1, d_ff=128,
                                causal=True, dropout=0.0, attn_impl="xla")
    p = tfm.init_params(jax.random.key(0), cfg)["blocks"][0]
    h = jnp.zeros((4, 16, d_model), cfg.compute_dtype)
    traced = tfm.head_major_blocks
    with (jax.sharding.set_mesh(mesh) if mesh is not None
          else contextlib.nullcontext()):
        jaxpr = jax.make_jaxpr(
            lambda p, h: tfm._block(cfg, p, h, None, None, False))(p, h)
    view = f"[{d_model},3,{n_heads},{cfg.head_dim}]"
    return str(jaxpr), view, tfm.head_major_blocks - traced


@pytest.mark.parametrize("where", ["no_mesh", "tp_1", "tp_2_over_3_heads"])
def test_without_a_head_axis_the_block_traces_as_one_matmul_and_a_split(where):
    """No mesh, a tp of 1, or a tp that divides no head count: flash keeps
    every head on every device there, so the projection is today's form (the
    one-chip cells' programs do not change) and nothing counts."""
    mesh, heads = {
        "no_mesh": (None, 4),
        "tp_1": (SpecLayout(data=4, fsdp=1, tp=1).build_mesh(jax.devices()[:4]), 4),
        "tp_2_over_3_heads": (_dp2tp2().mesh, 3),
    }[where]
    jaxpr, view, head_major = _traced_block(mesh, d_model=16 * heads,
                                            n_heads=heads)
    assert head_major == 0
    assert view not in jaxpr


def test_under_a_head_axis_the_block_contracts_with_the_head_major_view():
    jaxpr, view, head_major = _traced_block(_dp2tp2().mesh)
    assert head_major == 1
    assert view in jaxpr


# ------------------------------------------------- acceptance: loss parity


def test_fsdp_tp_matches_replicated_loss_curve():
    """ISSUE 9 acceptance: an fsdp×tp run matches the replicated run's loss
    curve to 1e-6 over ≥10 steps on the same seeded data."""
    a, b = _mlp(), _mlp()
    ta = ParallelTrainer(a, mesh=build_mesh(data=8))
    tb = ParallelTrainer(b, mesh_layout=SpecLayout(data=2, fsdp=2, tp=2))
    la, lb = [], []
    for ds in _batches(steps=10):
        ta._fit_batch(ds)
        tb._fit_batch(ds)
        la.append(a.score_)
        lb.append(b.score_)
    np.testing.assert_allclose(la, lb, atol=1e-6)
    # and the final params agree too (the updates really applied on shards)
    for wa, wb in zip(jax.tree.leaves(a.params_), jax.tree.leaves(b.params_)):
        np.testing.assert_allclose(np.asarray(wa), np.asarray(wb), atol=1e-5)


def test_graph_fsdp_training_matches_replicated():
    def graph():
        g = (NeuralNetConfiguration.Builder().seed(11).updater(Adam(1e-2))
             .graph_builder().add_inputs("in")
             .set_input_types(InputType.feed_forward(8)))
        g.add_layer("d1", DenseLayer(n_in=8, n_out=16, activation="tanh"), "in")
        g.add_layer("out", OutputLayer(n_in=16, n_out=4, activation="softmax",
                                       loss="mcxent"), "d1")
        g.set_outputs("out")
        return ComputationGraph(g.build()).init()

    a, b = graph(), graph()
    ta = ParallelTrainer(a, mesh=build_mesh(data=8))
    tb = ParallelTrainer(b, mesh_layout=SpecLayout(data=2, fsdp=2, tp=2))
    for ds in _batches(steps=5):
        ta._fit_batch(ds)
        tb._fit_batch(ds)
    np.testing.assert_allclose(float(a.score_), float(b.score_), atol=1e-6)


# --------------------------------------------------- shard byte accounting


def test_partition_shards_params_and_opt_state():
    net = _mlp()  # every dim divides 4 → fully sharded over fsdp×tp
    trainer = ParallelTrainer(net, mesh_layout=SpecLayout(data=2, fsdp=2, tp=2))
    trainer._place_net()
    rep = trainer.partition_report
    assert rep.uncovered == [] and rep.replicated_fallback == []
    # each device holds exactly nbytes/prod(sharded axes) of every leaf:
    # kernels split fsdp×tp (4-way), 1-D norms/biases split fsdp (2-way)
    mesh = trainer.mesh

    def shard_frac(spec):
        axes = [a for dim in spec if dim is not None
                for a in (dim if isinstance(dim, tuple) else (dim,))]
        return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1

    expected_dev = sum(w.nbytes // shard_frac(s)
                       for w, s in zip(jax.tree.leaves(net.params_),
                                       jax.tree.leaves(
                                           rep.specs,
                                           is_leaf=lambda x: isinstance(x, P))))
    assert rep.per_device_params_bytes == expected_dev
    # the 2-D kernels dominate → per-device bytes land well under total/2
    assert rep.per_device_params_bytes < rep.params_bytes_total // 2
    # Adam m/v shard identically to the params
    assert rep.opt_bytes_per_rank == 2 * rep.params_bytes_per_rank
    # donation sanity: a fit step updates in place on the shards and keeps
    # the sharding (no silent gather-to-replicated)
    trainer._fit_batch(_batches(steps=1)[0])
    w = net.params_["0"]["W"]
    assert w.sharding.spec == P("fsdp", "tp")

    from deeplearning4j_tpu.monitoring import get_registry

    snap = get_registry().snapshot()
    kinds = {s["labels"]["kind"]: s["value"]
             for s in snap["tdl_param_bytes_per_rank"]["series"]}
    assert kinds["params"] == rep.params_bytes_per_rank
    assert kinds["opt_state"] == rep.opt_bytes_per_rank
    infos = snap["tdl_mesh_layout_info"]["series"]
    assert [s["labels"] for s in infos] == [{"data": "2", "fsdp": "2", "tp": "2"}]


def test_strict_partitioner_refuses_uncovered_params():
    part = Partitioner(SpecLayout(data=2, fsdp=2, tp=2))
    with pytest.raises(ValueError, match="does not cover.*mystery"):
        part.spec_tree({"0": {"mystery_param": np.zeros((4, 4), np.float32)}})


# ------------------------------------------------------------ batch sharding


def test_batch_sharding_generalizes_to_layout_meshes():
    # ISSUE 9 satellite: multi-axis mesh → batch over data, REPLICATED over
    # fsdp/tp; 1-axis mesh under any name keeps the historical behavior
    layout_mesh = SpecLayout(data=2, fsdp=2, tp=2).build_mesh()
    assert batch_sharding(layout_mesh).spec == P("data")
    one_axis = build_mesh(model=8)
    assert batch_sharding(one_axis).spec == P("model")
    no_data = SpecLayout(data=1, fsdp=4, tp=2).build_mesh()
    # degenerate data axis still present → still P("data") (size-1 split)
    assert batch_sharding(no_data).spec == P("data")
    import jax.numpy as jnp
    from jax.sharding import Mesh

    pure_model = Mesh(np.array(jax.devices()).reshape(4, 2), ("x", "y"))
    assert batch_sharding(pure_model).spec == P()  # no data axis: replicate
    # and a placement through it actually works
    out = jax.device_put(jnp.ones((8, 3)), batch_sharding(layout_mesh))
    assert out.sharding.spec == P("data")


# ------------------------------------------------- layout-aware checkpoints


def test_sharded_checkpoint_roundtrip_and_layout_mismatch(tmp_path):
    from deeplearning4j_tpu.serde.checkpoint import TrainingCheckpointer

    a = _mlp()
    ta = ParallelTrainer(a, mesh_layout=SpecLayout(data=2, fsdp=2, tp=2))
    for ds in _batches(steps=4):
        ta._fit_batch(ds)
    ck = ta.checkpointer(str(tmp_path), async_write=False)
    ck.save(a)

    # same layout: restore places shards directly (no host assembly)
    b = _mlp(seed=99)  # different init — must be fully overwritten
    tb = ParallelTrainer(b, mesh_layout=SpecLayout(data=2, fsdp=2, tp=2))
    assert tb.checkpointer(str(tmp_path), async_write=False).restore(b)
    tb._place_net()
    for wa, wb in zip(jax.tree.leaves(a.params_), jax.tree.leaves(b.params_)):
        np.testing.assert_array_equal(np.asarray(wa), np.asarray(wb))
        assert wb.sharding.spec == wa.sharding.spec
    for ua, ub in zip(jax.tree.leaves(a.updater_state),
                      jax.tree.leaves(b.updater_state)):
        np.testing.assert_array_equal(np.asarray(ua), np.asarray(ub))
    assert b.iteration == a.iteration

    # training continues bit-for-bit from the restored shards
    ds = _batches(steps=5)[-1]
    ta._fit_batch(ds)
    tb._fit_batch(ds)
    np.testing.assert_allclose(float(a.score_), float(b.score_), atol=1e-7)

    # mismatched layout: clear error NAMING BOTH layouts
    c = _mlp()
    tc = ParallelTrainer(c, mesh_layout=SpecLayout(data=1, fsdp=4, tp=2))
    with pytest.raises(ValueError) as ei:
        tc.checkpointer(str(tmp_path), async_write=False).restore(c)
    msg = str(ei.value)
    assert "data=2 x fsdp=2 x tp=2" in msg and "data=1 x fsdp=4 x tp=2" in msg

    # replicated restore of a sharded checkpoint is also a (named) mismatch
    with pytest.raises(ValueError, match="replicated"):
        TrainingCheckpointer(str(tmp_path), async_write=False).restore(_mlp())


def test_replicated_checkpoint_still_restores_under_a_partitioner(tmp_path):
    """A layout-less (replicated) checkpoint loads into a sharded trainer:
    assemble host-side, then _place_net shards it — the upgrade path from a
    replicated gang to a sharded one."""
    from deeplearning4j_tpu.serde.checkpoint import TrainingCheckpointer

    a = _mlp()
    ParallelTrainer(a, mesh=build_mesh(data=8))._fit_batch(_batches(1)[0])
    TrainingCheckpointer(str(tmp_path), async_write=False).save(a)

    b = _mlp(seed=99)
    tb = ParallelTrainer(b, mesh_layout=SpecLayout(data=2, fsdp=2, tp=2))
    # place (and fit) BEFORE restoring: the one-shot _place_net is already
    # spent, so the restore itself must re-shard the assembled arrays
    tb._fit_batch(_batches(1)[0])
    assert tb.checkpointer(str(tmp_path), async_write=False).restore(b)
    assert b.params_["0"]["W"].sharding.spec == P("fsdp", "tp")
    for ua in jax.tree.leaves(b.updater_state):
        assert hasattr(ua.sharding, "spec")  # opt state re-placed too
    for wa, wb in zip(jax.tree.leaves(a.params_), jax.tree.leaves(b.params_)):
        np.testing.assert_allclose(np.asarray(wa), np.asarray(wb), atol=0)


# ------------------------------------------------------------- coverage gate


def _bundled_nets():
    """Representative bundled models exercising every param-producing layer
    family: zoo CNNs, recurrent stacks, embeddings, attention, the extended
    layers, and a ComputationGraph."""
    from deeplearning4j_tpu.models.zoo import LeNet, SimpleCNN
    from deeplearning4j_tpu.nn.attention_layers import (
        LearnedSelfAttentionLayer, SelfAttentionLayer)
    from deeplearning4j_tpu.nn.conf import (Bidirectional, EmbeddingLayer,
                                            GlobalPoolingLayer,
                                            SeparableConvolution2D, SimpleRnn)
    from deeplearning4j_tpu.nn.layers_ext import (CenterLossOutputLayer,
                                                  GRULayer, PReLULayer)
    from deeplearning4j_tpu.nn.layers_tail import GravesBidirectionalLSTM

    yield LeNet(input_shape=(1, 12, 12)).init()
    yield SimpleCNN(input_shape=(3, 16, 16)).init()

    rnn = (NeuralNetConfiguration.Builder().seed(5).updater(Adam(1e-3)).list()
           .layer(EmbeddingSequenceLayer(n_in=32, n_out=8))
           .layer(LSTM(n_in=8, n_out=8))
           .layer(GravesLSTM(n_in=8, n_out=8, peephole=True))
           .layer(GRULayer(n_in=8, n_out=8))
           .layer(GravesBidirectionalLSTM(n_in=8, n_out=8))
           .layer(Bidirectional(fwd=SimpleRnn(n_in=8, n_out=8)))
           .layer(RnnOutputLayer(n_in=16, n_out=4, activation="softmax",
                                 loss="mcxent"))
           .set_input_type(InputType.recurrent(32, 6))
           .build())
    yield MultiLayerNetwork(rnn).init()

    attn = (NeuralNetConfiguration.Builder().seed(5).updater(Adam(1e-3)).list()
            .layer(SelfAttentionLayer(n_heads=2, n_out=8, project_input=True))
            .layer(LearnedSelfAttentionLayer(n_heads=2, n_out=8, n_queries=4,
                                             project_input=True))
            .layer(GlobalPoolingLayer(pooling_type="avg"))
            .layer(PReLULayer())
            .layer(CenterLossOutputLayer(n_in=8, n_out=4))
            .set_input_type(InputType.recurrent(8, 6))
            .build())
    yield MultiLayerNetwork(attn).init()

    cnn_ext = (NeuralNetConfiguration.Builder().seed(5).updater(Adam(1e-3)).list()
               .layer(SeparableConvolution2D(n_out=8, kernel_size=(3, 3),
                                             convolution_mode="same"))
               .layer(BatchNormalization())
               .layer(DenseLayer(n_out=16, activation="relu"))
               .layer(OutputLayer(n_out=4, activation="softmax", loss="mcxent"))
               .set_input_type(InputType.convolutional(8, 8, 2))
               .build())
    yield MultiLayerNetwork(cnn_ext).init()


def test_spec_layout_covers_bundled_model_params():
    """ISSUE 9 satellite (the coverage gate): SpecLayout must assign a role
    to EVERY param name the bundled models produce — an unmatched name would
    silently replicate, eating the memory the partitioner exists to save.
    New layers must extend nn.conf param-role tagging to pass this."""
    for net in _bundled_nets():
        missing = uncovered_params(net.params_, param_role_tree(net))
        assert not missing, (
            f"{type(net).__name__} params with no partition role "
            f"(tag them in nn.conf / Layer.param_roles): {missing}")


def test_spec_layout_covers_functional_transformer_params():
    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       init_params,
                                                       init_qa_head)

    cfg = TransformerConfig.tiny(n_layers=2)
    params = init_params(jax.random.key(0), cfg)
    missing = uncovered_params(params, param_role_tree(params))
    assert not missing, missing
    qa = init_qa_head(jax.random.key(1), cfg)
    assert not uncovered_params(qa, param_role_tree(qa))


# ------------------------------------------------------------- donation lint


_DONATE_SCAN = ("parallel",)
_DONATE_FILES = ("nn/multilayer.py", "nn/graph.py", "models/transformer.py")


def test_fused_step_compilations_donate_buffers():
    """ISSUE 9 satellite (repo lint): every ``jax.jit`` in the parallel/
    package and the fused-step modules must pass ``donate_argnums`` —
    an un-donated (params, opt-state) compilation doubles peak memory and
    silently defeats in-place sharded updates. Non-donating sites that are
    genuinely read-only (inference executables) carry a ``# donate-ok:``
    justification."""
    files = [p for d in _DONATE_SCAN for p in sorted((ROOT / d).rglob("*.py"))]
    files += [ROOT / f for f in _DONATE_FILES]
    offenders = []
    for path in files:
        rel = path.relative_to(ROOT).as_posix()
        src = path.read_text()
        lines = src.splitlines()
        tree = ast.parse(src, filename=rel)
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "jit"
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "jax"):
                continue
            if any(kw.arg == "donate_argnums" for kw in node.keywords):
                continue
            if "donate-ok" in lines[node.lineno - 1]:
                continue
            offenders.append(f"{rel}:{node.lineno}")
    assert not offenders, (
        "jax.jit without donate_argnums in a fused-step module (donate the "
        "params/opt-state, or justify a read-only executable with "
        f"`# donate-ok: <reason>`): {offenders}")
