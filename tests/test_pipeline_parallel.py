"""Cost-model-balanced pipeline parallelism (ISSUE 19).

The tentpole acceptance, pinned as tier-1 tests:

- pipelined-vs-single-device loss parity at 1e-6 RELATIVE over a real
  ``pipe=2`` CPU mesh (fp32 compute: bf16's 1-ULP encode jitter is 3e-2
  at loss magnitude 8 and would make any 1e-6 bar meaningless);
- GPipe and 1F1B are token-identical: bitwise-equal losses, gradients
  equal to AD noise;
- the schedule's bubble is pinned STRUCTURALLY (scan trip counts in the
  jaxpr: forward fills+drains in ``M+S-1`` ticks, the 1F1B backward in
  ``M+2S-1``) — no flaky wall-clock asserts for a compile-time property;
- stage partitions come from the min-max cost partitioner (hand-computed
  pins), ragged depth without boundaries fails LOUDLY naming both
  numbers, measured skew re-partitions via the same partitioner;
- a ``pipe=2`` checkpoint restores onto ``fsdp=2`` (and back) BITWISE
  via ``reshard=True``, and refuses without it;
- peak temp bytes under remat stop scaling with depth beyond the
  param-linear floor (grad accumulators scale with L by construction —
  the honest flatness claim is about the ACTIVATION slope).
"""

import ast
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   init_params, loss_fn)
from deeplearning4j_tpu.monitoring import flight, get_registry
from deeplearning4j_tpu.monitoring.costmodel import (balance_stages,
                                                     stage_costs,
                                                     xla_step_cost)
from deeplearning4j_tpu.monitoring.flight import FlightRecorder
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.parallel.partition import (PipelinePartitioner,
                                                   SpecLayout, largest_layout)
from deeplearning4j_tpu.parallel.pipeline import (PipelineParallelTrainer,
                                                  _PipelineNet,
                                                  canonical_pp_params,
                                                  pipeline_transformer_params,
                                                  stage_index_map,
                                                  transformer_pp_loss_fn,
                                                  uniform_boundaries)
from deeplearning4j_tpu.parallel.supervisor import GangSupervisor
from deeplearning4j_tpu.parallel.trainer import ParallelTrainer

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _cfg(n_layers=6, d_model=16, seq=32, remat=False):
    return TransformerConfig(
        vocab_size=64, max_len=seq, d_model=d_model, n_heads=2,
        n_layers=n_layers, d_ff=2 * d_model, dropout=0.0,
        param_dtype=jnp.float32, compute_dtype=jnp.float32, remat=remat)


def _batch(cfg, B=8, T=16, seed=0):
    rs = np.random.RandomState(seed)
    return {
        "tokens": jnp.asarray(rs.randint(0, cfg.vocab_size, (B, T)), jnp.int32),
        "labels": jnp.asarray(rs.randint(0, cfg.vocab_size, (B, T)), jnp.int32),
    }


def _mesh(dp=2, pipe=2):
    devs = np.array(jax.devices()[: dp * pipe]).reshape(dp, pipe)
    return Mesh(devs, ("dp", "pipe"))


def _counter_value(name):
    snap = get_registry().snapshot().get(name) or {}
    return sum(s["value"] for s in snap.get("series") or [])


# ------------------------------------------------- cost-model stage partition


class TestStagePartition:
    def test_min_max_split_matches_hand_computed(self):
        # [1,1,1,3] @ 2: cut@3 -> max(3,3)=3 beats cut@2 -> max(2,4)=4
        assert balance_stages([1, 1, 1, 3], 2) == [(0, 3), (3, 4)]
        assert stage_costs([1, 1, 1, 3], [(0, 3), (3, 4)]) == [3.0, 3.0]
        # heavy head: one fat layer alone, the three light ones together
        assert balance_stages([3, 1, 1, 1], 2) == [(0, 1), (1, 4)]
        # uniform costs recover the uniform split
        assert balance_stages([1] * 6, 2) == [(0, 3), (3, 6)]
        # 2x-skewed front half moves one layer across the cut
        assert balance_stages([2, 2, 2, 1, 1, 1], 2) == [(0, 2), (2, 6)]

    def test_tied_splits_resolve_deterministically_earliest_cut(self):
        # [1,1,1] @ 2: cut@1 and cut@2 both cost max=2 — the DP must pin
        # ONE answer or rebalancing would flap between equal splits
        assert balance_stages([1, 1, 1], 2) == [(0, 1), (1, 3)]

    def test_ragged_depth_without_boundaries_raises_naming_both(self):
        cfg = _cfg(n_layers=5)
        params = init_params(jax.random.key(0), cfg)
        with pytest.raises(ValueError) as ei:
            pipeline_transformer_params(params, 2)
        msg = str(ei.value)
        assert "5 layers" in msg and "2 pipeline stages" in msg
        assert "balance_stages" in msg  # the fix is named, not just the crash

    def test_ragged_depth_with_cost_boundaries_works(self):
        cfg = _cfg(n_layers=5)
        params = init_params(jax.random.key(0), cfg)
        bounds = balance_stages([1.0] * 5, 2)
        out = pipeline_transformer_params(params, 2, boundaries=bounds)
        # canonical [L, ...] passthrough — the staged view is built in the
        # compiled step from the static index map, not here
        assert jax.tree.leaves(out["blocks"])[0].shape[0] == 5

    def test_uniform_boundaries_and_index_map_validation(self):
        assert uniform_boundaries(6, 2) == [(0, 3), (3, 6)]
        idx, valid = stage_index_map([(0, 2), (2, 5)])
        assert idx.shape == (2, 3) and valid.shape == (2, 3)
        assert valid.tolist() == [[1.0, 1.0, 0.0], [1.0, 1.0, 1.0]]
        with pytest.raises(ValueError, match="contiguous"):
            stage_index_map([(0, 2), (3, 5)])

    def test_largest_layout_claims_pipe_first(self):
        assert largest_layout(8, pipe=2) == SpecLayout(
            data=1, fsdp=4, tp=1, pipe=2)
        # non-dividing pipe preference degrades instead of failing
        assert largest_layout(7, pipe=2) == SpecLayout(data=1, fsdp=7, tp=1)
        assert largest_layout(8, pipe=2).build_mesh().devices.size == 8

    def test_supervisor_carries_pipe_preference(self, tmp_path):
        sup = GangSupervisor("mod:fn", n_processes=2, pipe_stages=2,
                             workdir=str(tmp_path))
        assert sup.pipe_stages == 2


# --------------------------------------------------------------- loss parity


class TestLossParity:
    @pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
    def test_pipelined_loss_matches_single_device_1e6(self, schedule):
        cfg = _cfg(n_layers=6)
        params = init_params(jax.random.key(0), cfg)
        batch = _batch(cfg)
        ref = float(jax.jit(lambda p, b: loss_fn(p, b, cfg))(params, batch))

        mesh = _mesh(dp=2, pipe=2)
        bounds = balance_stages([1.0] * 6, 2)
        pp_loss = transformer_pp_loss_fn(cfg, 4, mesh, pipe_axis="pipe",
                                         schedule=schedule, boundaries=bounds)
        got = float(jax.jit(pp_loss)(canonical_pp_params(params), batch))
        assert abs(got - ref) / abs(ref) <= 1e-6

    def test_gpipe_and_1f1b_token_identical(self):
        """Same fill-drain forward — losses BITWISE equal; the 1F1B
        custom-vjp backward agrees with GPipe's AD transpose to AD noise."""
        cfg = _cfg(n_layers=6)
        pparams = canonical_pp_params(init_params(jax.random.key(0), cfg))
        batch = _batch(cfg)
        mesh = _mesh(dp=2, pipe=2)
        bounds = balance_stages([1.0] * 6, 2)

        losses, grads = {}, {}
        for schedule in ("gpipe", "1f1b"):
            f = transformer_pp_loss_fn(cfg, 4, mesh, pipe_axis="pipe",
                                       schedule=schedule, boundaries=bounds)
            l, g = jax.jit(jax.value_and_grad(f))(pparams, batch)
            losses[schedule], grads[schedule] = float(l), g
        assert losses["gpipe"] == losses["1f1b"]  # bitwise
        for a, b in zip(jax.tree.leaves(grads["gpipe"]),
                        jax.tree.leaves(grads["1f1b"])):
            scale = max(1.0, float(jnp.max(jnp.abs(a))))
            assert float(jnp.max(jnp.abs(a - b))) / scale <= 1e-8


# ------------------------------------------------- schedule structure (ticks)


def _scan_lengths(jaxpr):
    """All ``lax.scan`` trip counts in a jaxpr, recursively."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(int(eqn.params["length"]))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None:
                    out += _scan_lengths(inner)
                elif hasattr(sub, "eqns"):
                    out += _scan_lengths(sub)
    return out


class TestScheduleTicks:
    """The bubble of a fill-drain schedule is a COMPILE-TIME property: the
    tick scan's trip count. Pinning it in the jaxpr proves the measured
    bubble can't exceed the analytic bound by construction — (ticks - M)
    idle slots out of ticks — without a single wall-clock measurement."""

    def test_forward_runs_m_plus_s_minus_1_ticks(self):
        cfg = _cfg(n_layers=6)
        pparams = canonical_pp_params(init_params(jax.random.key(0), cfg))
        batch = _batch(cfg)
        mesh = _mesh(dp=2, pipe=2)
        bounds = balance_stages([1.0] * 6, 2)
        M, S = 4, 2
        for schedule in ("gpipe", "1f1b"):
            f = transformer_pp_loss_fn(cfg, M, mesh, pipe_axis="pipe",
                                       schedule=schedule, boundaries=bounds)
            lengths = _scan_lengths(jax.make_jaxpr(f)(pparams, batch).jaxpr)
            assert M + S - 1 in lengths, (schedule, lengths)

    def test_1f1b_backward_runs_m_plus_2s_minus_1_ticks(self):
        cfg = _cfg(n_layers=6)
        pparams = canonical_pp_params(init_params(jax.random.key(0), cfg))
        batch = _batch(cfg)
        mesh = _mesh(dp=2, pipe=2)
        bounds = balance_stages([1.0] * 6, 2)
        M, S = 4, 2
        lengths = {}
        for schedule in ("gpipe", "1f1b"):
            f = transformer_pp_loss_fn(cfg, M, mesh, pipe_axis="pipe",
                                       schedule=schedule, boundaries=bounds)
            lengths[schedule] = _scan_lengths(
                jax.make_jaxpr(jax.grad(f))(pparams, batch).jaxpr)
        # 1F1B's combined bwd+recompute scan: one pass of M + 2S - 1 ticks
        assert M + 2 * S - 1 in lengths["1f1b"], lengths["1f1b"]
        # GPipe has no such scan — its backward is the AD transpose of the
        # forward's M + S - 1 tick loop
        assert M + 2 * S - 1 not in lengths["gpipe"], lengths["gpipe"]


# ------------------------------------------------------------------- trainer


class TestPipelineTrainer:
    def test_guard_plain_trainer_rejects_pipe_layout(self):
        cfg = _cfg(n_layers=6)
        net = _PipelineNet(canonical_pp_params(init_params(jax.random.key(0), cfg)))
        with pytest.raises(ValueError, match="pipe"):
            ParallelTrainer(net, mesh_layout=PipelinePartitioner(
                SpecLayout(data=4, pipe=2)))

    def test_pipeline_trainer_rejects_pipe_1(self):
        cfg = _cfg(n_layers=6)
        with pytest.raises(ValueError, match="pipe"):
            PipelineParallelTrainer(
                init_params(jax.random.key(0), cfg), cfg, Adam(1e-3),
                SpecLayout(data=4, fsdp=2), n_microbatches=4)

    def test_trains_profiles_and_rebalances(self, tmp_path, monkeypatch):
        """One trainer exercised end to end (compiles amortized): cost-model
        boundaries at construction, two real 1F1B steps, measured stage
        seconds within 15% of the cost-model prediction, a forced-skew
        rebalance that MOVES the split + bumps the counter + records the
        flight event, and a post-rebalance step through the recompiled
        index map."""
        cfg = _cfg(n_layers=6)
        trainer = PipelineParallelTrainer(
            init_params(jax.random.key(0), cfg), cfg, Adam(1e-3),
            SpecLayout(data=4, pipe=2), n_microbatches=4, schedule="1f1b")
        assert trainer.boundaries == [(0, 3), (3, 6)]  # balanced uniform

        # B=16: microbatch size (B/M = 4) must divide the data axis (4)
        batch = _batch(cfg, B=16)
        trainer._fit_batch(batch)
        l0 = float(trainer.net.score_)
        trainer._fit_batch(batch)
        l1 = float(trainer.net.score_)
        assert np.isfinite(l0) and np.isfinite(l1) and l1 < l0
        assert trainer.net.iteration == 2

        # measured per-stage seconds vs the cost model: uniform layers,
        # 3|3 split -> predicted fractions 0.5/0.5; measured must agree
        # within the 15% acceptance bar (compared as fractions so a
        # loaded CI host's common slowdown divides out). Stage times are
        # 1-2 ms of CPU wall clock beside five other test workers: one
        # preempted repeat is not a wrong cost model, so measure up to
        # three times
        pred = trainer.predicted_stage_costs()
        p_frac = [c / sum(pred) for c in pred]
        for _ in range(3):
            times = trainer.profile_stages(seq=32, batch_size=2, repeats=6)
            off = max(abs(t / sum(times) - p) / p for t, p in zip(times, p_frac))
            if off <= 0.15:
                break
        assert off <= 0.15, (times, pred)

        # balanced timings -> no rebalance
        assert trainer.maybe_rebalance([1.0, 1.0]) is None
        assert trainer.boundaries == [(0, 3), (3, 6)]

        # forced 2x skew on stage 0 -> the partitioner moves one layer
        rec = FlightRecorder(proc="pp-test")
        flight.set_flight_recorder(rec)
        try:
            before = _counter_value("tdl_pipe_rebalances_total")
            new = trainer.maybe_rebalance([2.0, 1.0])
            assert new == [(0, 2), (2, 6)]
            assert trainer.boundaries == new
            assert _counter_value("tdl_pipe_rebalances_total") == before + 1
            evs = [e for e in rec.events() if e["kind"] == "pipe_rebalance"]
            assert len(evs) == 1
            assert evs[0]["old_boundaries"] == [[0, 3], [3, 6]]
            assert evs[0]["new_boundaries"] == [[0, 2], [2, 6]]
            assert evs[0]["skew"] == pytest.approx(2.0 / 1.5)
        finally:
            flight.set_flight_recorder(None)

        # the recompiled step trains on the new split
        trainer._fit_batch(batch)
        assert np.isfinite(float(trainer.net.score_))
        assert trainer.net.iteration == 3


# ----------------------------------------------------- lifecycle: pipe↔fsdp


class TestPipeFsdpReshard:
    def test_pipe2_to_fsdp2_roundtrip_bitwise(self, tmp_path):
        """A pipe=2 checkpoint restores onto fsdp=2 bitwise with
        ``reshard=True`` (both layouts chunk the same leading layer dim),
        refuses loudly without it, and survives the round trip back."""
        cfg = _cfg(n_layers=6)
        ta = PipelineParallelTrainer(
            init_params(jax.random.key(0), cfg), cfg, Adam(1e-3),
            SpecLayout(data=4, pipe=2), n_microbatches=4)
        ta._fit_batch(_batch(cfg, B=16))  # non-trivial params + Adam slots
        ck = ta.checkpointer(str(tmp_path), async_write=False)
        assert ck.save(ta.net)

        def fresh_net(seed):
            p = canonical_pp_params(init_params(jax.random.key(seed), cfg))
            return _PipelineNet(p, Adam(1e-3).init(p))

        # mismatched layout without reshard=True: loud refusal, not mixing
        fsdp_part = PipelinePartitioner(SpecLayout(data=4, fsdp=2))
        nb = fresh_net(7)
        from deeplearning4j_tpu.serde.checkpoint import TrainingCheckpointer
        with pytest.raises(ValueError) as ei:
            TrainingCheckpointer(str(tmp_path), partitioner=fsdp_part,
                                 async_write=False).restore(nb)
        assert "reshard=True" in str(ei.value)

        # pipe=2 -> fsdp=2, bitwise
        assert TrainingCheckpointer(str(tmp_path), partitioner=fsdp_part,
                                    async_write=False,
                                    reshard=True).restore(nb)
        for a, b in zip(jax.tree.leaves(ta.net.params_),
                        jax.tree.leaves(nb.params_)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(ta.net.updater_state),
                        jax.tree.leaves(nb.updater_state)):
            assert np.array_equal(np.asarray(a), np.asarray(b))

        # and back: fsdp=2 -> pipe=2, still bitwise vs the original
        ck2 = TrainingCheckpointer(str(tmp_path / "b"), partitioner=fsdp_part,
                                   async_write=False)
        assert ck2.save(nb)
        nc = fresh_net(9)
        pipe_part = PipelinePartitioner(SpecLayout(data=4, pipe=2))
        assert TrainingCheckpointer(str(tmp_path / "b"),
                                    partitioner=pipe_part, async_write=False,
                                    reshard=True).restore(nc)
        for a, b in zip(jax.tree.leaves(ta.net.params_),
                        jax.tree.leaves(nc.params_)):
            assert np.array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------- remat memory flatness


class TestRematMemory:
    def test_activation_slope_flat_under_remat(self):
        """Per added layer, remat must free every block's INTERNALS and keep
        at most the layer-boundary carries — both bounds computed from the
        shapes, not picked.

        ``memory_analysis`` temp bytes at depth L and 2L give a slope per
        layer. Part of it is parameter-sized by construction (grad
        accumulators, the take-view) and equal with and without remat; the
        rest is activations. Sizes are chosen so activations dominate
        (tokens per microbatch >> d_model): at the toy size this test used
        before, parameter-sized temporaries were 4x the activations and the
        ratio it asserted measured XLA's buffer assignment, not remat (on a
        TPU v5e that size reports temp_bytes == 0; at BERT width the chip
        shows the same picture as here — CHANGES.md, PR 23)."""
        M, S, dp = 4, 2, 2
        B, T, D, H = 32, 64, 32, 2
        mesh = _mesh(dp=dp, pipe=S)
        stats = {}
        for remat in (False, True):
            for L in (4, 8):
                cfg = _cfg(n_layers=L, d_model=D, seq=T, remat=remat)
                pparams = canonical_pp_params(
                    init_params(jax.random.key(0), cfg))
                batch = _batch(cfg, B=B, T=T)
                f = transformer_pp_loss_fn(
                    cfg, M, mesh, pipe_axis="pipe", schedule="1f1b",
                    boundaries=balance_stages([1.0] * L, S))
                stats[(remat, L)] = xla_step_cost(
                    jax.jit(jax.grad(f)), pparams, batch)

        def per_layer(remat, key):
            return (stats[(remat, 8)][key] - stats[(remat, 4)][key]) / 4.0

        temp_nomat = per_layer(False, "temp_bytes")
        temp_remat = per_layer(True, "temp_bytes")
        param_floor = per_layer(True, "argument_bytes")

        # from the shapes (fp32, d_ff = 2*d_model in _cfg): one microbatch on
        # one device is `seqs` sequences of T tokens
        seqs = B // M // dp
        tokens, F, itemsize = seqs * T, 2 * D, 4
        # what a block must keep for its own backward when nothing is
        # recomputed — q/k/v, both ffn hiddens, the attention probabilities
        # (a lower bound: layernorm outputs and residuals come on top)
        internals = (tokens * (3 * D + 2 * F) + seqs * H * T * T) * itemsize
        # what remat keeps instead: the block-input carry, once per
        # microbatch the 1F1B schedule holds in flight
        in_flight = min(M, 2 * S - 1)
        carries = in_flight * tokens * D * itemsize

        assert temp_nomat - temp_remat >= internals, (
            temp_nomat, temp_remat, internals)
        assert temp_remat - param_floor <= carries, (
            temp_remat, param_floor, carries)


# ------------------------------------------------------------------ AST lint


_LINT_FILES = ("deeplearning4j_tpu", "bench.py")


def _boundary_literal_offenders(src: str, rel: str):
    """Hardcoded stage-boundary literals: a ``boundaries=[(..)]`` keyword
    or a ``boundaries = [(..)]`` assignment whose value is a LITERAL
    list/tuple. Boundaries must come from the cost partitioner
    (``balance_stages`` / ``transformer_stage_boundaries``) or arrive as
    an explicit argument; a ``# stage-ok: <reason>`` on the line (or the
    line above) justifies genuine fixtures."""
    lines = src.splitlines()

    def _excused(lineno):
        return any("stage-ok" in ln
                   for ln in lines[max(0, lineno - 2):lineno])

    offenders = []
    for node in ast.walk(ast.parse(src, filename=rel)):
        if isinstance(node, ast.Call):
            for kw in node.keywords:
                if (kw.arg == "boundaries"
                        and isinstance(kw.value, (ast.List, ast.Tuple))
                        and kw.value.elts
                        and not _excused(node.lineno)):
                    offenders.append(f"{rel}:{node.lineno} (call)")
        elif isinstance(node, ast.Assign):
            names = [t.attr if isinstance(t, ast.Attribute) else
                     getattr(t, "id", "") for t in node.targets]
            if ("boundaries" in names
                    and isinstance(node.value, (ast.List, ast.Tuple))
                    and node.value.elts
                    and not _excused(node.lineno)):
                offenders.append(f"{rel}:{node.lineno} (assign)")
    # ast.walk is breadth-first; report in source order
    return sorted(offenders, key=lambda s: int(s.split(":")[1].split()[0]))


def test_no_hardcoded_stage_boundaries_in_package():
    """ISSUE 19 satellite (repo lint): stage boundaries in the package and
    bench come from the cost-model partitioner or an explicit argument —
    one convenient hardcoded split would silently defeat the balancing
    the pipe axis exists for."""
    offenders = []
    for entry in _LINT_FILES:
        path = ROOT / entry
        files = sorted(path.rglob("*.py")) if path.is_dir() else [path]
        for f in files:
            rel = f.relative_to(ROOT).as_posix()
            offenders += _boundary_literal_offenders(f.read_text(), rel)
    assert not offenders, (
        "hardcoded stage-boundary literal (derive it from "
        "monitoring.costmodel.balance_stages / pass it through, or justify "
        f"a fixture with `# stage-ok: <reason>`): {offenders}")


def test_stage_boundary_lint_catches_a_planted_offender():
    planted = (
        "def f(run, bounds):\n"
        "    run(boundaries=[(0, 1), (1, 6)])\n"
        "    run(boundaries=bounds)\n"
        "    run(boundaries=[(0, 3)])  # stage-ok: test fixture\n"
        "    other = 1\n"
        "    boundaries = [(0, 2), (2, 4)]\n"
        "    boundaries = compute()\n"
    )
    hits = _boundary_literal_offenders(planted, "planted.py")
    assert hits == ["planted.py:2 (call)", "planted.py:6 (assign)"]
