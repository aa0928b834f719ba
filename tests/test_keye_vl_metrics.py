"""ISSUE 35: the readers and work functions that the keye-vl-2-30b-a3b cell
adds to the benchmark, on hand-made observations: a value where the program
records what they read, ``None`` where it does not (the parent commit, a
training cell, the other families). Work is held to numbers worked by hand at
Keye-VL-2.0-30B-A3B's published widths. Then the check that decides
``correct`` at the rehearsal sizes, sound and under each of its four
controls."""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import stepaccount, work_keye_vl as wk  # noqa: E402

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "keye-vl-2-30b-a3b.longdoc-qa"

# benchmark/models/keye_vl.py:shapes at the cell's sizes
M = {"hidden": 2048, "heads": 32, "kv_heads": 4, "head_dim": 128,
     "index_heads": 16, "index_dim": 64, "topk": 2048, "expert_width": 768,
     "router_width": 128, "resident_experts": 128, "experts_per_token": 8,
     "layers": 6, "vocab": 151936, "slots": 16, "block_T": 32, "max_len": 17408,
     "weight_bytes": 2}

# 100 steps of 5 live slots at ~9,000 rows (282 blocks) each; in every layer
# 35 of the 128 experts touched by the 40 token-expert pairs
COUNTERS = {"moe_experts_resident": 128 * 6 * 100, "moe_routed_tokens": 5 * 6 * 100,
            "kv_blocks_read": 5 * 282 * 100, "kv_blocks_mapped": 16 * 544 * 100,
            "moe_experts_touched": 35 * 6 * 100, "moe_resident_assignments": 40 * 6 * 100,
            "moe_load_max": 3 * 6 * 100, "moe_load_sum": 40 * 6 * 100}
# the whole run's row counters: 2,048 of 9,000 rows selected
CUMULATIVE = {"dsa_live_rows": 9000 * 6 * 5 * 1000, "dsa_selected_rows": 2048 * 6 * 5 * 1000}
LIVE_ROWS = (5 * 282 - 2.5) * 32          # 45,040
SELECTED = LIVE_ROWS * 2048 / 9000


def _read(metric, obs):
    spec = importlib.util.spec_from_file_location(
        "metric_under_test_" + metric.replace(".", "_"),
        os.path.join(ROOT, "benchmark", "metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(obs)


def test_parameter_counts_are_the_issues_table():
    assert wk.attention_params(M) == 2 * 8_388_608 + 2 * 1_048_576 == 18_874_368
    assert wk.indexer_params(M) == 2_097_152 + 131_072 + 32_768 == 2_260_992
    assert wk.expert_params(M) == 4_718_592
    layer = 18_874_368 + 2_260_992 + 2048 * 128 + 128 * 4_718_592
    assert layer == 625_377_280                       # 1.251 GB in bfloat16
    assert 48 * layer + 2 * 2048 * 151936 == 30_640_439_296


def test_per_step_means_from_the_counters():
    mean = wk.per_step(M, COUNTERS, CUMULATIVE)
    assert mean == {"steps": 100.0, "live_slots": 5.0, "live_rows": LIVE_ROWS,
                    "selected_rows": pytest.approx(SELECTED), "touched": 210.0,
                    "assignments": 240.0}
    assert wk.per_step(M, COUNTERS)["selected_rows"] is None   # no row counters
    assert wk.per_step(M, {}) is None and wk.per_step(M, None) is None
    assert wk.per_step(M, {"kv_blocks_read": 5}) is None  # no expert counters


def test_work_of_the_selection_the_attention_the_experts_and_a_step():
    sf, sb = wk.select_work(M, live_slots=5, live_rows=LIVE_ROWS)
    assert (sf, sb) == (2 * 16 * 64 * LIVE_ROWS, LIVE_ROWS * 64 * 2 + 5 * 16 * (64 * 2 + 4))
    af, ab = wk.attend_work(M, live_slots=5, selected_rows=SELECTED)
    assert (af, ab) == (pytest.approx(4 * 32 * 128 * SELECTED),
                        pytest.approx(SELECTED * 1024 * 2 + 5 * 2 * 4096 * 2))
    ef, eb = wk.expert_matmul_work(M, touched=210, assignments=240)
    assert (ef, eb) == (240 * 2 * 4_718_592, 210 * 4_718_592 * 2)
    mean = {k: v for k, v in wk.per_step(M, COUNTERS, CUMULATIVE).items() if k != "steps"}
    flops, nbytes = wk.decode_step_work(M, **mean)
    always = 6 * (18_874_368 + 2_260_992) + 2048 * 151936
    router = 6 * 2048 * 128
    assert nbytes == pytest.approx(always * 2 + router * 4 + eb + 6 * (sb + ab))
    assert flops == pytest.approx(2 * 5 * (always + router) + ef + 6 * (sf + af))
    # ISSUE 35's arithmetic: ~3 GB a step, 3.7 ms at 819 GB/s; bytes bind
    assert nbytes == pytest.approx(3.025e9, rel=1e-3)
    assert nbytes / 819e9 == pytest.approx(3.694e-3, rel=1e-3)
    assert nbytes / 819e9 > flops / 197e12
    # with full attention K/V alone would be live_rows x 2 KB a layer: 0.55 GB
    assert 6 * LIVE_ROWS * 2048 == pytest.approx(0.553e9, rel=1e-2)


FAMILY = {"shapes": M, "traced_counters": COUNTERS,
          "step_device_s": [0.011, 0.012, 0.013, 0.030]}
STATS = {"serve": {"executor_stats": {"blocks": {
    **COUNTERS, **CUMULATIVE, "kv_cache_bytes_per_token": 13824}},
    "window": {"records": [
        {"id": "a", "in_window": True, "ok": True, "prompt": 8000},
        {"id": "b", "in_window": True, "ok": True, "prompt": 16000},
        {"id": "c", "in_window": True, "ok": True, "prompt": 4000},
        {"id": "d", "in_window": False, "ok": True, "prompt": 4000}]},
    "spans": {"a": {"code": 200, "phases": {"prefill": 0.4}},
              "b": {"code": 200, "phases": {"prefill": 1.6}},
              "c": {"code": 200, "phases": {"prefill": 0.1}},
              "d": {"code": 200, "phases": {"prefill": 9.0}}}}}
TRACE_OPS = [
    # the decode program's selection: index keys gathered, scored, sorted
    ["fusion:fusion bf16[8704,32,128]", 0.010],
    ["fusion:fusion f32[16,17408]", 0.002],
    ["sort:sort f32[16,17408] s32[16,17408]", 0.018],
    # its attention: the loop over live slots, and (inside its time) a trip's
    # gather, which is not counted twice
    ["while:while s32[] bf16[16,4096] s32[] s32[16]", 0.050],
    ["fusion:fusion bf16[2048,512]", 0.040],
    # the rest of the step and a prefill: neither's
    ["while:while s32[] f32[16,2048] s32[] s32[128]", 0.09],
    ["tpu_custom_call:dsa_selected_attn bf16[512,4096]", 0.5],
    ["sort:sort f32[16,128] s32[16,128]", 0.001]]
FULL = {**STATS, "peaks": V5E, "family": FAMILY,
        "trace": {"mosaic_calls": {}, "device_ops": TRACE_OPS}}
# the kimi_k2 family's pool: expert counters, but no selection
OTHER = {"peaks": V5E, "family": {"shapes": {"hidden": 7168, "layers": 7},
                                  "traced_counters": COUNTERS,
                                  "step_device_s": [0.01]},
         "trace": {"mosaic_calls": {}, "device_ops": [["fusion:fusion f32[64,7168]", 0.5]]},
         "serve": {"executor_stats": {"blocks": dict(COUNTERS)},
                   "window": {"records": []}, "spans": {}}}

STEP_BYTES = 3_025_140_840.1066666


@pytest.mark.parametrize("metric,expected", [
    ("step.mfu.decode.dsa", 100.0 * (STEP_BYTES / 819e9) / 0.0125),
    ("dsa.select_roofline.decode",
     100.0 * 100 * 6 * ((LIVE_ROWS * 128 + 5 * 16 * 132) / 819e9) / 0.030),
    ("dsa.attend_roofline.decode",
     100.0 * 100 * 6 * ((SELECTED * 2048 + 5 * 16384) / 819e9) / 0.050),
    ("dsa.selected_row_share", 100.0 * 2048 / 9000),
    ("dsa.prefill_ms_per_ktok", 50.0),       # median of 50, 100, 25
])
def test_reader_on_a_hand_made_observation(metric, expected):
    assert _read(metric, FULL) == pytest.approx(expected, rel=1e-9)
    assert 0.0 < _read(metric, FULL) <= 100.0
    # where the program records none of it the line leaves the metric out
    for obs in (OTHER, {"serve": None, "train": {}}, {}):
        assert _read(metric, obs) is None


def test_traced_readers_need_traced_steps_counters_and_the_row_counts():
    traced = ("step.mfu.decode.dsa", "dsa.select_roofline.decode",
              "dsa.attend_roofline.decode")
    assert _read(traced[0], {**FULL, "family": {**FAMILY, "step_device_s": []}}) is None
    for metric in traced:
        assert _read(metric, {**FULL, "family": {**FAMILY, "traced_counters": None}}) is None
        no_rows = {**FULL, "serve": {**FULL["serve"], "executor_stats": {
            "blocks": dict(COUNTERS)}}}
        assert _read(metric, no_rows) is None
    for metric in traced[1:]:
        assert _read(metric, {**FULL, "trace": None}) is None
        assert _read(metric, {**FULL, "trace": {"mosaic_calls": {}, "device_ops": [
            ["fusion:fusion f32[64,7168]", 0.5]]}}) is None


# -- BENCHMARK.json's entries for the cell -------------------------------------


def test_the_cell_is_declared_with_the_issues_readers_and_judged_on_p90_and_serve_tok_s():
    """As ISSUE 35 lists them: the cell is on ``serve_tok_s`` and
    ``serve_lat_per_tok_p90_ms`` (not the median), on the nine accepted
    readers that find something to read in it, and its new readers move what
    the issue says: four p90, the selection's row share ``serve_tok_s``."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert CELL in [w["name"] for w in bench["workloads"]]   # later PRs append theirs
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    lists = {m["name"]: m.get("workloads") for g in ("end_to_end", "per_layer")
             for m in bench[g]}
    on = {name for name, cells in lists.items() if cells and CELL in cells}
    assert on == {
        "serve_tok_s", "serve_lat_per_tok_p90_ms",
        "gen.lateness_p99_ms", "sched.queue_wait_p50_ms", "sched.ttft_p50_ms",
        "kv.block_occupancy", "kv.step_host_ms", "kv.cache_bytes_per_token",
        "kv.step_overlap_share",  # ISSUE 38: the four serving cells
        *stepaccount.READERS,     # ISSUE 39: the step account's ten, likewise
        "device.peak_mem_frac.serve", "moe.experts_touched_share", "moe.load_max_over_mean",
        "step.mfu.decode.dsa", "dsa.select_roofline.decode",
        "dsa.attend_roofline.decode", "dsa.selected_row_share",
        "dsa.prefill_ms_per_ktok"}
    new = [m for m in bench["per_layer"] if m["name"].startswith(("dsa.", "step.mfu.decode.dsa"))]
    assert [m["workloads"] for m in new] == [[CELL]] * 5
    assert {m["name"]: m["moves"] for m in new} == {
        "step.mfu.decode.dsa": "serve_lat_per_tok_p90_ms",
        "dsa.select_roofline.decode": "serve_lat_per_tok_p90_ms",
        "dsa.attend_roofline.decode": "serve_lat_per_tok_p90_ms",
        "dsa.selected_row_share": "serve_tok_s",
        "dsa.prefill_ms_per_ktok": "serve_lat_per_tok_p90_ms"}
    for m in new:  # a reader for each, beside the accepted ones
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics", m["name"] + ".py"))
    config = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                         "keye-vl-2-30b-a3b.json")))
    assert config["reduced"] == ["num_hidden_layers"] == list(config["reduced_why"])
    assert config["published"]["num_hidden_layers"] == 48 and config["num_hidden_layers"] == 6
    assert {"qk_norm", "indexer_k_norm", "indexer_scales", "indexer_rope",
            "indexer_input", "indexer_chunks"} <= set(config["assumed"])


# -- the check that decides ``correct``, and its controls ----------------------


@pytest.fixture(scope="module")
def rehearsed():
    """The cell at its rehearsal sizes: (ctx, family adapter, config file,
    traffic, sound weights)."""
    import types

    import jax

    from benchmark import run as bench_run
    from benchmark.models import keye_vl as family

    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    _, config, traffic = bench_run.find_cell(bench, CELL, True)
    lines = []
    ctx = types.SimpleNamespace(config=config, traffic=traffic, emit=lines.append,
                                lines=lines)
    cfg = family.build_config(config, on_tpu=False, max_len=int(traffic["max_len"]))
    params = jax.jit(family.make_init(cfg))(jax.random.key(11))
    return ctx, family, params


@pytest.mark.parametrize("control", ["fp8_experts", "drop_expert"])
def test_a_weights_control_faults_the_first_layers_experts_and_shares_the_rest(
        rehearsed, control):
    import jax
    import numpy as np

    _, family, params = rehearsed
    faulty = family.control_params(params, control)
    same = jax.tree.map(lambda a, b: a is b, params, faulty)
    assert all(jax.tree.leaves({**same, "layers": same["layers"][1:]}))
    layer = {k: v for k, v in same["layers"][0].items() if k != "experts"}
    assert all(jax.tree.leaves(layer))
    moved = {n: np.asarray(a != b).any(axis=(1, 2)) for (n, a), b in zip(
        params["layers"][0]["experts"].items(), faulty["layers"][0]["experts"].values())}
    if control == "fp8_experts":
        assert all(m.all() for m in moved.values())
    else:
        assert moved["wd"].tolist() == [True] + [False] * (len(moved["wd"]) - 1)
        assert not moved["wg"].any() and not moved["wu"].any()
    with pytest.raises(ValueError, match="unknown control"):
        family.control_params(params, "int4")
    # the two faults of behaviour serve the sound weights
    assert family.control_params(params, "no_selection") is params


@pytest.mark.parametrize("control,caught_by", [
    (None, ()),
    ("no_selection", ("select_flip_distance_max",)),
    ("stale_index_keys", ("cache_row_err_first_layer_max", "cache_row_err_decode_steps_max")),
    ("fp8_experts", ("expert_part_rel_err", "expert_part_rel_err_decode_rows")),
    ("drop_expert", ("expert_part_rel_err", "expert_part_rel_err_decode_rows")),
    ("decode_no_selection", ("decode_select_flip_distance_max",)),
    ("decode_wrong_rows", ("decode_attend_rel_err",)),
])
def test_the_check_passes_the_sound_program_and_fails_each_control(
        rehearsed, control, caught_by, monkeypatch):
    """Through ``check_served_path`` itself, as ``runners/serve_family.py``
    drives it: the pool serves the fault (of the weights, or of the
    configuration ``build_config`` makes under the control's name), the
    reference keeps the sound weights and the published selection, and the
    check says not correct by the limits that fault is meant to trip."""
    import numpy as np

    from benchmark.runners.serve_family import CONTROL_ENV
    from deeplearning4j_tpu.models.paged_decode import PagedDecodeSlotPool

    ctx, family, params = rehearsed
    t = ctx.traffic
    if control:
        monkeypatch.setenv(CONTROL_ENV, control)
    cfg = family.build_config(ctx.config, on_tpu=False, max_len=int(t["max_len"]))
    served = family.control_params(params, control) if control else params
    pool = PagedDecodeSlotPool(served, cfg, slots=int(t["slots"]),
                               block_T=int(t["block_T"]), max_len=int(t["max_len"]))
    ok = family.check_served_path(ctx, pool, cfg, served, np.random.RandomState(3),
                                  reference_params=params)
    line = ctx.lines[-1]
    assert line["line"] == "check" and line["correct"] == ok == (control is None)
    assert line["slots_live_together"] == len(t["check"]["prompt_lens"]) + len(
        t["check"]["bystander_lens"])
    limits = {"select_flip_distance_max": "select_margin",
              "cache_row_err_first_layer_max": "cache_first_layer_rtol",
              "cache_row_err_decode_steps_max": "cache_step_rtol",
              "cache_row_err_median": "cache_median_rtol",
              "attend_rel_err": "attend_rtol",
              "decode_select_flip_distance_max": "select_margin",
              "decode_attend_rel_err": "decode_attend_rtol",
              "expert_part_rel_err": "expert_rtol",
              "expert_part_rel_err_decode_rows": "expert_rtol"}
    over = {k for k, limit in limits.items() if line[k] > line[limit]}
    # the limits the fault is meant to trip do; the sound program trips none
    assert set(caught_by) <= over and (control or not over), (over, line)
    assert line["decode_queries"] > 0 and (control or not line["decode_select_wrong_queries"])
    if control and control.startswith("decode_"):
        # a fault of the step's select-and-attend alone: what prefill's
        # functions compute on the reference's input does not see it
        assert not {"select_flip_distance_max", "attend_rel_err",
                    "cache_row_err_first_layer_max"} & over, (over, line)


# one query over ten rows, scores 9 .. 0 and -inf (unseen), topk 4: the
# reference keeps rows 0-3; a selection is the list of rows the program kept
@pytest.mark.parametrize("mine,flips,distance", [
    ((0, 1, 2, 3), 0, 0.0),           # equal sets
    ((0, 1, 2, 4), 2, 1 / 4),         # the two rows at the edge swapped
    ((0, 1, 3, 4), 2, 2 / 4),         # dropped: row 2, two rows from the edge
    ((0, 1, 2, 8), 2, 5 / 4),         # added: row 8, the fifth row left out
    ((1, 2, 3, 4), 2, 4 / 4),         # dropped: the best row of all
    (tuple(range(9)), 5, 5 / 4),      # every seen row kept (no selection)
    ((0, 1, 2, 3, 9), 0, 0.0),        # an unseen row does not count
])
def test_flip_distance_counts_rows_of_the_references_order(mine, flips, distance):
    import jax.numpy as jnp
    import numpy as np

    from benchmark.models.keye_vl import edge_stats

    scores = np.array([5., 9., 7., 8., 4., 3., 2., 1., 0., -np.inf])  # not sorted
    order = np.argsort(-scores, kind="stable")  # order[r]: the row of rank r
    I = jnp.asarray(scores)[None, None]
    theirs = np.zeros(10, bool)
    theirs[order[:4]] = True
    kept = np.zeros(10, bool)
    kept[order[list(mine)]] = True
    full = jnp.ones((1, 1), bool)
    got = edge_stats(I, jnp.asarray(theirs)[None, None], jnp.asarray(kept)[None, None],
                     full, 4)
    assert int(got["flips"]) == flips and float(got["flip_distance_max"]) == distance
    # a query that does not count (padding, or one that leaves nothing out)
    idle = edge_stats(I, jnp.asarray(theirs)[None, None], jnp.asarray(kept)[None, None],
                      ~full, 4)
    assert int(idle["flips"]) == 0 and float(idle["flip_distance_max"]) == 0.0
