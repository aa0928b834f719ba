"""ISSUE 37: the readers and work functions that the trinity-large-preview
cell adds to the benchmark, on hand-made observations: a value where the
program records what they read, ``None`` where it does not (the parent commit,
a training cell, the other families). Work is held to numbers worked by hand
at Trinity-Large-Preview's published widths. Then the configuration's file
against the catalog's keys, and the check that decides ``correct`` at the
rehearsal sizes, sound and under each of its seven controls."""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import stepaccount, work_trinity as wt  # noqa: E402

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "trinity-large-preview.mixed-len"

# benchmark/models/trinity.py:shapes at the cell's sizes
M = {"hidden": 3072, "heads": 48, "kv_heads": 8, "head_dim": 128, "window": 4096,
     "dense_width": 12288, "expert_width": 3072, "router_width": 256,
     "resident_experts": 32, "experts_per_token": 4, "layers": 5, "full_layers": 1,
     "sparse_layers": 4, "vocab": 25024, "slots": 16, "block_T": 32, "weight_bytes": 2}

# 100 steps of 6 live slots: 282 blocks each under a full layer (~9,000 rows),
# 129 under a sliding one; in every expert layer 3 of the 32 experts touched
# by the 3 token-expert pairs that landed here
COUNTERS = {"moe_experts_resident": 32 * 4 * 100, "moe_routed_tokens": 6 * 4 * 100,
            "kv_blocks_read": 6 * 282 * 100, "kv_blocks_read_windowed": 6 * 129 * 100,
            "kv_blocks_mapped": 16 * 1056 * 100,
            "moe_experts_touched": 3 * 4 * 100, "moe_resident_assignments": 3 * 4 * 100,
            "moe_load_max": 1 * 4 * 100, "moe_load_sum": 3 * 4 * 100}
FULL_ROWS = (6 * 282 - 3) * 32            # 54,048
WINDOW_ROWS = (6 * 129 - 6) * 32          # 24,576 = 6 x 4,096
CUMULATIVE = {"swa_rows_read": 1_000 * (9000 + 4 * 4096), "swa_rows_windowless": 1_000 * 5 * 9000,
              "kv_window_blocks_freed": 77}


def _read(metric, obs):
    spec = importlib.util.spec_from_file_location(
        "metric_under_test_" + metric.replace(".", "_"),
        os.path.join(ROOT, "benchmark", "metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(obs)


def test_parameter_counts_are_the_issues_table():
    assert wt.attention_params(M) == 3 * 18_874_368 + 2 * 3_145_728 == 62_914_560
    assert wt.swiglu_params(M, 12288) == 113_246_208
    assert wt.swiglu_params(M, 3072) == 28_311_552
    dense = 62_914_560 + 113_246_208
    expert = 62_914_560 + 28_311_552 + 3072 * 256 + 32 * 28_311_552
    assert (dense, expert) == (176_160_768, 997_982_208)
    held = dense + 4 * expert + 2 * 25024 * 3072
    assert held == 4_321_837_056 and held * 2 == pytest.approx(8.64e9, rel=1e-3)
    # the two cache groups at 16 slots x 33,792 positions, blocks of 32
    full = (1 + 16 * 1056) * 32 * 1024 * 2 * 2 * 1
    sliding = (1 + 16 * 130) * 32 * 1024 * 2 * 2 * 4
    assert full + sliding == pytest.approx(3.31e9, rel=2e-3)
    assert 16 * 33792 * 4096 * 5 == pytest.approx(11.07e9, rel=1e-3)   # a uniform arena


def test_per_step_means_from_the_counters():
    assert wt.per_step(M, COUNTERS) == {
        "steps": 100.0, "live_slots": 6.0, "full_rows": FULL_ROWS,
        "window_rows": WINDOW_ROWS, "touched": 12.0, "assignments": 12.0}
    # short slots: a sliding layer sees no more than a full one
    short = {**COUNTERS, "kv_blocks_read": 6 * 10 * 100, "kv_blocks_read_windowed": 6 * 10 * 100}
    mean = wt.per_step(M, short)
    assert mean["window_rows"] <= mean["full_rows"] == (60 - 3) * 32
    assert wt.per_step(M, {}) is None and wt.per_step(M, None) is None
    no_window = {k: v for k, v in COUNTERS.items() if k != "kv_blocks_read_windowed"}
    assert wt.per_step(M, no_window) is None     # another family's pool


def test_work_of_a_decode_call_the_experts_and_a_step():
    ff, fb = wt.decode_attn_work(M, live_slots=6, rows=FULL_ROWS)
    assert (ff, fb) == (4 * 48 * 128 * FULL_ROWS, FULL_ROWS * 2 * 1024 * 2 + 6 * 2 * 6144 * 2)
    sf, sb = wt.decode_attn_work(M, live_slots=6, rows=WINDOW_ROWS)
    ef, eb = wt.expert_matmul_work(M, touched=12, assignments=12)
    assert (ef, eb) == (12 * 2 * 28_311_552, 12 * 28_311_552 * 2)
    mean = {k: v for k, v in wt.per_step(M, COUNTERS).items() if k != "steps"}
    flops, nbytes = wt.decode_step_work(M, **mean)
    always = 5 * 62_914_560 + 113_246_208 + 4 * 28_311_552 + 3072 * 25024
    router = 4 * 3072 * 256
    assert nbytes == pytest.approx(always * 2 + router * 4 + eb + fb + 4 * sb)
    assert flops == pytest.approx(2 * 6 * (always + router) + ef + ff + 4 * sf)
    # ISSUE 37's arithmetic: 1.24 GB of non-expert weights a step (1.5 ms at
    # 819 GB/s), ~57 MB a touched expert, 4 KB a visible row and layer
    assert always * 2 == pytest.approx(1.236e9, rel=1e-3)
    assert always * 2 / 819e9 == pytest.approx(1.51e-3, rel=1e-2)
    assert eb / 12 == pytest.approx(56.6e6, rel=1e-2)
    assert (fb - 6 * 2 * 6144 * 2) / FULL_ROWS == 4096
    assert nbytes / 819e9 > flops / 197e12     # bytes bind a step


def test_visible_pairs_and_a_prefill_calls_work():
    assert wt.visible_pairs(5, None) == 15 == wt.visible_pairs(5, 8)
    assert wt.visible_pairs(10, 4) == 10 + 6 * 4            # 1+2+3+4, then 4 a query
    assert wt.visible_pairs(4096, 4096) == 4096 * 4097 / 2
    n = 32768
    assert wt.visible_pairs(n, 4096) == 4096 * 4097 / 2 + (n - 4096) * 4096
    # a sliding layer of a 32k prompt computes an eighth of... 4.27x fewer pairs
    assert wt.visible_pairs(n, None) / wt.visible_pairs(n, 4096) == pytest.approx(4.27, rel=1e-2)
    flops, nbytes = wt.prefill_attn_work(M, n=n, window=4096)
    assert flops == 4 * 128 * 48 * wt.visible_pairs(n, 4096)
    assert nbytes == 2 * n * 6144 * 2 + 2 * n * 1024 * 2
    assert flops / 197e12 > nbytes / 819e9     # compute binds prefill attention
    assert wt.call_bucket("tpu_custom_call:flash_fwd_swa bf16[1,8192,6144]") == 8192
    assert wt.call_bucket("fusion:fusion f32[16]") is None
    assert [wt.bucket_of(n, 32) for n in (1, 32, 33, 256, 257, 9000, 32768)] == [
        32, 32, 64, 256, 512, 16384, 32768]


FAMILY = {"shapes": M, "traced_counters": COUNTERS,
          "step_device_s": [0.0040, 0.0042, 0.0044, 0.0300]}
STATS = {"serve": {"executor_stats": {"blocks": {
    **COUNTERS, **CUMULATIVE, "kv_cache_bytes_per_token": 20480}},
    "window": {"records": [
        {"id": "a", "in_window": True, "ok": True, "prompt": 8000},
        {"id": "b", "in_window": True, "ok": True, "prompt": 6000},
        {"id": "c", "in_window": True, "ok": True, "prompt": 500},
        {"id": "d", "in_window": False, "ok": True, "prompt": 500}]},
    "spans": {"a": {"code": 200, "phases": {"prefill": 0.4}},
              "b": {"code": 200, "phases": {"prefill": 0.15}},
              "c": {"code": 200, "phases": {"prefill": 0.05}},
              "d": {"code": 200, "phases": {"prefill": 9.0}}}}}
MOSAIC = {
    # 100 steps x 5 layers of the decode kernel
    "tpu_custom_call:paged_decode_attn f32[768,128]": {"calls": 500, "seconds": 0.200},
    # two prefills of the 8192 bucket (prompts 8000 and 6000), one of 512
    # (the prompts 500), one of 2048 (nobody's in the window: a warm-up's):
    # four sliding layers each
    "tpu_custom_call:flash_fwd_swa bf16[1,8192,6144]": {"calls": 8, "seconds": 0.080},
    "tpu_custom_call:flash_fwd_swa bf16[1,512,6144]": {"calls": 4, "seconds": 0.001},
    "tpu_custom_call:flash_fwd_swa bf16[1,2048,6144]": {"calls": 4, "seconds": 0.004},
    # the full layer's calls are another kernel's
    "tpu_custom_call:flash_fwd_gqa bf16[1,8192,6144]": {"calls": 2, "seconds": 0.050}}
FULL = {**STATS, "peaks": V5E, "family": FAMILY,
        "trace": {"mosaic_calls": MOSAIC, "device_ops": []}}
# the kimi_k2 family's pool: expert counters, but no windowed cache group
OTHER = {"peaks": V5E, "family": {"shapes": {"hidden": 7168, "layers": 7},
                                  "traced_counters": {k: v for k, v in COUNTERS.items()
                                                      if k != "kv_blocks_read_windowed"},
                                  "step_device_s": [0.01]},
         "trace": {"mosaic_calls": {"tpu_custom_call:paged_mla_decode_attn": {
             "calls": 7, "seconds": 0.1}}, "device_ops": []},
         "serve": {"executor_stats": {"blocks": {k: v for k, v in COUNTERS.items()
                                                 if k != "kv_blocks_read_windowed"}},
                   "window": {"records": []}, "spans": {}}}


def _prefill_least(n):
    """The roofline of one windowed prefill call: compute binds a long prompt,
    q / o / K / V traffic a prompt of a few hundred tokens."""
    return max(4 * 128 * 48 * wt.visible_pairs(n, 4096) / 197e12,
               (2 * n * 6144 * 2 + 2 * n * 1024 * 2) / 819e9)


def _expected():
    mean = {k: v for k, v in wt.per_step(M, COUNTERS).items() if k != "steps"}
    _, step_bytes = wt.decode_step_work(M, **mean)
    full = (FULL_ROWS * 4096 + 6 * 2 * 6144 * 2) / 819e9
    window = (WINDOW_ROWS * 4096 + 6 * 2 * 6144 * 2) / 819e9
    prefill = (8 * (_prefill_least(8000) + _prefill_least(6000)) / 2
               + 4 * _prefill_least(500) + 4 * _prefill_least(2048))
    return {
        "step.mfu.decode.swa": 100.0 * (step_bytes / 819e9) / 0.0043,
        "swa.decode_attn_roofline": 100.0 * 500 * (0.2 * full + 0.8 * window) / 0.200,
        "swa.prefill_attn_roofline": 100.0 * prefill / 0.085,
        "swa.visible_row_share": 100.0 * (9000 + 4 * 4096) / (5 * 9000),
        "swa.prefill_ms_per_ktok": 50.0,       # median of 50, 25, 100
    }


@pytest.mark.parametrize("metric", sorted(_expected()))
def test_reader_on_a_hand_made_observation(metric):
    assert _read(metric, FULL) == pytest.approx(_expected()[metric], rel=1e-9)
    assert 0.0 < _read(metric, FULL) <= 100.0
    # where the program records none of it the line leaves the metric out
    for obs in (OTHER, {"serve": None, "train": {}}, {}):
        assert _read(metric, obs) is None


def test_traced_readers_need_traced_steps_counters_and_their_kernels_calls():
    traced = ("step.mfu.decode.swa", "swa.decode_attn_roofline")
    assert _read(traced[0], {**FULL, "family": {**FAMILY, "step_device_s": []}}) is None
    for metric in traced:
        assert _read(metric, {**FULL, "family": {**FAMILY, "traced_counters": None}}) is None
    for metric in ("swa.decode_attn_roofline", "swa.prefill_attn_roofline"):
        assert _read(metric, {**FULL, "trace": None}) is None
        assert _read(metric, {**FULL, "trace": {"mosaic_calls": {}, "device_ops": []}}) is None
    # a bucket that only a warm-up reached is held to the bucket's own length
    only = {**FULL, "trace": {"mosaic_calls": {
        "tpu_custom_call:flash_fwd_swa bf16[1,2048,6144]": {"calls": 4, "seconds": 0.004}},
        "device_ops": []}}
    assert _read("swa.prefill_attn_roofline", only) == pytest.approx(
        100.0 * 4 * _prefill_least(2048) / 0.004)


# -- BENCHMARK.json's entries for the cell, and the configuration's file --------


def test_the_cell_is_declared_with_the_issues_readers_and_judged_on_p90_and_serve_tok_s():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and (cell["config"], cell["traffic"]) == (
        "trinity-large-preview", "mixed-len")
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    lists = {m["name"]: m.get("workloads") for g in ("end_to_end", "per_layer")
             for m in bench[g]}
    on = {name for name, cells in lists.items() if cells and CELL in cells}
    new = {"step.mfu.decode.swa": "serve_lat_per_tok_p90_ms",
           "swa.decode_attn_roofline": "serve_lat_per_tok_p90_ms",
           "swa.prefill_attn_roofline": "serve_lat_per_tok_p90_ms",
           "swa.visible_row_share": "serve_tok_s",
           "swa.prefill_ms_per_ktok": "serve_lat_per_tok_p90_ms"}
    assert on == {
        "serve_tok_s", "serve_lat_per_tok_p90_ms",
        "gen.lateness_p99_ms", "sched.queue_wait_p50_ms", "sched.ttft_p50_ms",
        "kv.block_occupancy", "kv.step_host_ms", "kv.cache_bytes_per_token",
        "kv.step_overlap_share",  # ISSUE 38: the four serving cells
        *stepaccount.READERS,     # ISSUE 39: the step account's ten, likewise
        "device.peak_mem_frac.serve", "moe.experts_touched_share",
        "moe.resident_assignment_share", "moe.load_max_over_mean", *new}
    mine = [m for m in bench["per_layer"] if m["name"] in new]
    names = [m["name"] for m in bench["per_layer"]]
    assert names[names.index("step.mfu.decode.swa"):][:5] == list(new)   # appended together
    assert [m["workloads"] for m in mine] == [[CELL]] * 5
    assert {m["name"]: m["moves"] for m in mine} == new
    for m in mine:
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics", m["name"] + ".py"))


def test_the_configuration_file_holds_every_published_key():
    """The catalog's row (model-configs guide), key for key: the four cuts of
    scale differ, and they are listed; ``layer_types`` is kept whole."""
    kinds = (["sliding_attention"] * 3 + ["full_attention"]) * 15
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 3072, "intermediate_size": 12288, "layer_types": kinds,
        "load_balance_coeff": 5e-05, "max_position_embeddings": 262144,
        "model_type": "afmoe", "moe_intermediate_size": 3072, "mup_enabled": True,
        "n_group": 1, "num_attention_heads": 48, "num_dense_layers": 6,
        "num_expert_groups": 1, "num_experts": 256, "num_experts_per_tok": 4,
        "num_hidden_layers": 60, "num_key_value_heads": 8, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 10000, "route_norm": True, "route_scale": 2.448,
        "score_func": "sigmoid", "sliding_window": 4096, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192}
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "trinity-large-preview")
    config = json.load(open(os.path.join(ROOT, entry["file"])))
    differs = sorted(k for k, v in published.items() if config.get(k, "absent") != v)
    cuts = ["num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    assert differs == sorted(cuts) and entry["reduced"] == config["reduced"] == cuts
    assert list(config["reduced_why"]) == cuts
    assert {k: config["published"][k] for k in cuts} == {k: published[k] for k in cuts}
    assert {k: config[k] for k in cuts} == {
        "num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 32, "vocab_size": 25024}
    assert config["model"]["router_width"] == 256 and config["family"] == "trinity"
    for key in ("assumed", "departures", "deployment", "rehearse"):
        assert config[key]
    traffic = json.load(open(os.path.join(ROOT, "benchmark", "traffic", "mixed-len.json")))
    assert traffic["slots"] in (16, 12) and traffic["block_T"] == 32
    assert traffic["max_len"] == 33792 and traffic["shared_prefix_tokens"] == 0
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 2048,
                                        "sigma": 1.2, "min": 256, "max": 32768}
    assert traffic["answer_tokens"] == {"dist": "lognormal", "median": 192,
                                        "sigma": 0.5, "min": 64, "max": 1024}
    assert traffic["check"]["prompt_lens"] == [1500, 9000]
    assert traffic["check"]["decode_steps"] >= 40


def test_build_config_is_the_cut_at_published_widths():
    from benchmark.models import trinity as family

    config = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                         "trinity-large-preview.json")))
    cfg = family.build_config(config, on_tpu=True, max_len=33792)
    assert cfg.layer_types == ("sliding_attention",) * 3 + ("full_attention", "sliding_attention")
    assert (cfg.num_experts, cfg.n_resident_experts, cfg.expert_first) == (256, 32, 0)
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim, cfg.sliding_window) == (3072, 48, 8, 128, 4096)
    assert family.shapes(cfg, slots=16, block_T=32) == M
    fam = cfg.decode_family()
    assert fam.cache_groups == ((1, None), (4, 4096)) and fam.cache_widths == (1024,) * 4
    model = family.reference_model(config)
    assert model["layer_types"] == cfg.layer_types and model["expert_first"] == 0
    assert model["sliding_window"] == 4096 and model["route_scale"] == 2.448


# -- the check that decides ``correct``, and its controls ----------------------


@pytest.fixture(scope="module")
def rehearsed():
    """The cell at its rehearsal sizes: (ctx, family adapter, sound weights)."""
    import types

    import jax

    from benchmark import run as bench_run
    from benchmark.models import trinity as family

    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    _, config, traffic = bench_run.find_cell(bench, CELL, True)
    lines = []
    ctx = types.SimpleNamespace(config=config, traffic=traffic, emit=lines.append,
                                lines=lines)
    cfg = family.build_config(config, on_tpu=False, max_len=int(traffic["max_len"]))
    params = jax.jit(family.make_init(cfg))(jax.random.key(11))
    return ctx, family, params


@pytest.mark.parametrize("control", ["fp8_experts", "drop_expert"])
def test_a_weights_control_faults_the_first_expert_layer_and_shares_the_rest(
        rehearsed, control):
    import jax
    import numpy as np

    _, family, params = rehearsed
    faulty = family.control_params(params, control)
    same = jax.tree.map(lambda a, b: a is b, params, faulty)
    assert all(jax.tree.leaves({**same, "layers": same["layers"][:1] + same["layers"][2:]}))
    layer = {k: v for k, v in same["layers"][1].items() if k != "experts"}
    assert all(jax.tree.leaves(layer))
    moved = {n: np.asarray(a != b).any(axis=(1, 2)) for (n, a), b in zip(
        params["layers"][1]["experts"].items(), faulty["layers"][1]["experts"].values())}
    if control == "fp8_experts":
        assert all(m.all() for m in moved.values())
    else:
        assert moved["wd"].tolist() == [True] + [False] * (len(moved["wd"]) - 1)
        assert not moved["wg"].any() and not moved["wu"].any()
    with pytest.raises(ValueError, match="unknown control"):
        family.control_params(params, "int4")
    # the faults of behaviour serve the sound weights
    assert family.control_params(params, "no_window") is params


@pytest.mark.parametrize("control,caught_by", [
    (None, ()),
    ("no_window", ("attend_rel_err", "edge_rel_err", "decode_edge_rel_err")),
    ("window_off_by_one", ("edge_rel_err", "decode_edge_rel_err")),
    ("rope_on_global", ("attend_rel_err",)),
    ("no_gate", ("attend_rel_err",)),
    ("read_freed_block", ("decode_edge_rel_err",)),
    ("fp8_experts", ("expert_part_rel_err", "expert_part_rel_err_decode_rows")),
    ("drop_expert", ("expert_part_rel_err", "expert_part_rel_err_decode_rows")),
])
def test_the_check_passes_the_sound_program_and_fails_each_control(
        rehearsed, control, caught_by, monkeypatch):
    """Through ``check_served_path`` itself, as ``runners/serve_family.py``
    drives it: the pool serves the fault (of the weights, or of the
    configuration ``build_config`` makes under the control's name), the
    reference keeps the sound weights and the published equations, and the
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
    assert line["window_blocks_freed_in_the_steps"] > 0
    limits = {"cache_row_err_first_layer_max": "cache_first_layer_rtol",
              "cache_row_err_decode_steps_max": "cache_step_rtol",
              "cache_row_err_median": "cache_median_rtol",
              "attend_rel_err": "attend_rtol", "edge_rel_err": "edge_rtol",
              "decode_edge_rel_err": "decode_edge_rtol",
              "expert_part_rel_err": "expert_rtol",
              "expert_part_rel_err_decode_rows": "expert_rtol"}
    over = {k for k, limit in limits.items() if line[k] > line[limit]}
    # the limits the fault is meant to trip do; the sound program trips none
    assert set(caught_by) <= over and (control or not over), (over, line)
    if control is None:
        assert line["window_table_faults"] == 0 and line["routing_mismatched"] == 0
    if control == "read_freed_block":
        # a fault of the step's reach alone: what prefill's functions compute
        # on the reference's input does not see it
        assert not {"attend_rel_err", "edge_rel_err",
                    "cache_row_err_first_layer_max"} & over, (over, line)
