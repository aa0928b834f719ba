"""ISSUE 31: the readers and work functions that the kimi-k2-5 cell adds to
the benchmark, on hand-made observations: a value where the program records
what they read, ``None`` where it does not (the parent commit, a training
cell, the GPT-2 family). Work is held to numbers worked by hand at
Kimi-K2.5's published widths."""

import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import work_kimi_k2 as wk  # noqa: E402
from benchmark.runners import serve_family  # noqa: E402

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# benchmark/models/kimi_k2.py:shapes at the cell's sizes
M = {"hidden": 7168, "heads": 64, "q_rank": 1536, "kv_rank": 512, "nope": 128,
     "rope": 64, "v_dim": 128, "dense_width": 18432, "expert_width": 2048,
     "router_width": 384, "resident_experts": 12, "experts_per_token": 8,
     "layers": 7, "sparse_layers": 6, "vocab": 20480, "slots": 64,
     "block_T": 32, "weight_bytes": 2}

# 100 steps of 40 live slots at ~1,200 rows (38 blocks) each; in every sparse
# layer 8 of the 12 resident experts touched by 10 token-expert pairs
COUNTERS = {"moe_experts_resident": 12 * 6 * 100, "moe_routed_tokens": 40 * 6 * 100,
            "kv_blocks_read": 40 * 38 * 100, "kv_blocks_mapped": 64 * 160 * 100,
            "moe_experts_touched": 8 * 6 * 100, "moe_resident_assignments": 10 * 6 * 100,
            "moe_load_max": 3 * 6 * 100, "moe_load_sum": 10 * 6 * 100}


def _read(metric, obs):
    spec = importlib.util.spec_from_file_location(
        "metric_under_test_" + metric.replace(".", "_"),
        os.path.join(ROOT, "benchmark", "metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(obs)


def test_parameter_counts_are_the_issues_table():
    assert wk.attention_params(M) == (11_010_048 + 18_874_368 + 4_128_768
                                      + 8_388_608 + 58_720_256) == 101_122_048
    assert wk.swiglu_params(M, 2048) == 44_040_192
    assert wk.swiglu_params(M, 18432) == 396_361_728


def test_per_step_means_from_the_counters():
    mean = wk.per_step(M, COUNTERS)
    # rows: 38 blocks a slot less the half block an average slot overshoots by
    assert mean == {"live_slots": 40.0, "live_rows": (40 * 38 - 20) * 32,
                    "touched": 48.0, "assignments": 60.0}
    assert wk.per_step(M, {}) is None and wk.per_step(M, None) is None
    assert wk.per_step(M, {"kv_blocks_read": 5}) is None  # no expert counters


def test_work_of_a_kernel_call_the_expert_matmuls_and_a_step():
    mean = wk.per_step(M, COUNTERS)
    flops, nbytes = wk.mla_call_work(M, live_slots=40, live_rows=48_000)
    assert (flops, nbytes) == (2 * 64 * 48_000 * (576 + 512),
                               48_000 * 576 * 2 + 40 * 64 * (576 + 512) * 2)
    assert nbytes / 819e9 == pytest.approx(74.32e-6, rel=1e-3)  # bytes bind
    ef, eb = wk.expert_matmul_work(M, touched=48, assignments=60)
    assert (ef, eb) == (60 * 2 * 44_040_192, 48 * 44_040_192 * 2)
    sf, sb = wk.decode_step_work(M, **mean)
    always = 7 * 101_122_048 + 396_361_728 + 6 * 44_040_192 + 7168 * 20480
    router = 6 * 7168 * 384
    assert sb == always * 2 + router * 4 + eb + 7 * nbytes == 7_750_500_352
    assert sf == 2 * 40 * (always + router) + ef + 7 * flops
    assert sb / 819e9 == pytest.approx(9.463e-3, rel=1e-3)       # 7.75 GB a step


TRACE = {"mosaic_calls": {
             "tpu_custom_call:paged_mla_decode_attn bf16[4096,512]":
                 {"calls": 70, "seconds": 70 * 100e-6},
             "tpu_custom_call:flash_fwd bf16[64,1024,192] f32[64,1024,1]":
                 {"calls": 7, "seconds": 0.02}},
         "device_ops": [["while:while s32[] f32[64,7168] s32[] s32[12]", 0.04],
                        ["copy-done:copy-done bf16[7168,2048]", 0.02],
                        ["copy-done:copy-done bf16[2048,7168]", 0.01],
                        ["fusion:fusion f32[16,2048]", 0.03],   # inside a loop's time
                        ["fusion:fusion f32[64,7168]", 0.5],
                        ["while:while s32[] f32[2048,7168] s32[] s32[12]", 0.2]]}
FAMILY = {"shapes": M, "traced_counters": COUNTERS,
          "step_device_s": [0.011, 0.012, 0.013, 0.030]}
STATS = {"serve": {"executor_stats": {"blocks": {**COUNTERS,
                                                "kv_cache_bytes_per_token": 8960}}},
         "family": {"shapes": M}}
FULL = {**STATS, "trace": TRACE, "peaks": V5E, "family": FAMILY}
# the GPT-2 family's pool: blocks, but no expert counter and no family block
PLAIN = {"trace": {"mosaic_calls": {"tpu_custom_call:paged_decode_attn f32[16,1280]":
                                    {"calls": 36, "seconds": 1e-4}},
                   "device_ops": [["fusion:fusion f32[16]", 0.1]]},
         "peaks": V5E,
         "serve": {"executor_stats": {"blocks": {"kv_blocks_read": 3,
                                                "kv_blocks_mapped": 9}}}}


@pytest.mark.parametrize("metric,expected", [
    ("step.mfu.decode", 100.0 * (7_750_500_352 / 819e9) / 0.0125),
    ("paged_mla_decode_attn_roofline", 100.0 * 70 * (60_866_560 / 819e9) / 70e-4),
    ("moe.grouped_matmul_roofline.decode",
     100.0 * 10 * (48 * 44_040_192 * 2 / 819e9) / 0.07),
    ("moe.experts_touched_share", 100.0 * 8 / 12),
    ("moe.resident_assignment_share", 100.0 * 10 / (8 * 40)),
    ("moe.load_max_over_mean", 3 * 12 / 10),
    ("kv.cache_bytes_per_token", 8960),
])
def test_reader_on_a_hand_made_observation(metric, expected):
    assert _read(metric, FULL) == pytest.approx(expected, rel=1e-9)
    assert 0.0 < _read(metric, FULL) <= (100.0 if metric.endswith(
        ("roofline", "roofline.decode", "mfu.decode", "share")) else 1e9)
    # where the program records none of it the line leaves the metric out
    for obs in (PLAIN, {"serve": None, "train": {}}, {}):
        assert _read(metric, obs) is None


def test_traced_readers_need_traced_steps_and_counters():
    no_steps = {**FULL, "family": {**FAMILY, "step_device_s": []}}
    assert _read("step.mfu.decode", no_steps) is None
    no_counters = {**FULL, "family": {**FAMILY, "traced_counters": None}}
    for metric in ("step.mfu.decode", "paged_mla_decode_attn_roofline",
                   "moe.grouped_matmul_roofline.decode"):
        assert _read(metric, no_counters) is None
    untraced = {**FULL, "trace": None}
    assert _read("paged_mla_decode_attn_roofline", untraced) is None
    assert _read("moe.grouped_matmul_roofline.decode", untraced) is None


def test_a_steps_device_seconds_are_the_operations_since_the_last_program_ended():
    us = 1000.0
    events = [
        # a step the trace begins inside: nothing ended before it, left out
        ("host", "kv.step.fetch", 20 * us, 60 * us),
        ("device:0", "fusion.1", 10 * us, 50 * us),
        # step 1: its first operation STARTS before its dispatch span does
        # (the device's clock runs behind), after the last fetch ended at 80
        ("host", "kv.step.dispatch", 100 * us, 20 * us),
        ("host", "kv.step.fetch", 130 * us, 270 * us),
        ("device:0", "fusion.1", 95 * us, 100 * us),
        ("device:0", "fusion.2", 150 * us, 100 * us),
        ("device:0", "fusion.3", 320 * us, 30 * us),
        # a prefill between the steps belongs to neither
        ("host", "kv.prefill.fetch", 410 * us, 90 * us),
        ("device:0", "tpu_custom_call:flash_fwd", 420 * us, 40 * us),
        # step 2, after the prefill's fetch ended at 500
        ("host", "kv.step.dispatch", 505 * us, 10 * us),
        ("host", "kv.step.fetch", 520 * us, 100 * us),
        ("device:0", "fusion.1", 530 * us, 60 * us),
        ("device:1", "fusion.1", 530 * us, 90 * us),   # another chip: not counted
    ]
    assert serve_family.step_device_seconds(events) == pytest.approx(
        [185e-6, 60e-6])
    assert serve_family.step_device_seconds([]) == []


# -- the check that decides ``correct``, and its controls ----------------------


@pytest.fixture(scope="module")
def rehearsed():
    """The cell at its rehearsal sizes: (ctx, family adapter, cfg, weights)."""
    import types

    import jax

    from benchmark import run as bench_run
    from benchmark.models import kimi_k2 as family

    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    _, config, traffic = bench_run.find_cell(bench, "kimi-k2-5.agent-decode", True)
    lines = []
    ctx = types.SimpleNamespace(config=config, traffic=traffic, emit=lines.append,
                                lines=lines)
    cfg = family.build_config(config, on_tpu=False, max_len=int(traffic["max_len"]))
    params = jax.jit(family.make_init(cfg))(jax.random.key(11))
    return ctx, family, cfg, params


@pytest.mark.parametrize("control", ["fp8_experts", "drop_expert"])
def test_a_control_faults_the_first_sparse_layers_experts_and_shares_the_rest(
        rehearsed, control):
    import jax

    _, family, cfg, params = rehearsed
    faulty = family.control_params(params, control)
    at = cfg.first_k_dense_replace
    same = jax.tree.map(lambda a, b: a is b, params, faulty)
    assert all(jax.tree.leaves({**same, "layers": same["layers"][:at]
                                + same["layers"][at + 1:]}))
    layer = {k: v for k, v in same["layers"][at].items() if k != "experts"}
    assert all(jax.tree.leaves(layer))
    changed = [not all(jax.tree.leaves(e)) for e in same["layers"][at]["experts"]]
    assert changed == ([True] * len(changed) if control == "fp8_experts"
                       else [True] + [False] * (len(changed) - 1))
    with pytest.raises(ValueError, match="unknown control"):
        family.control_params(params, "int4")


@pytest.mark.parametrize("control,part", [(None, None), ("fp8_experts", "experts"),
                                          ("drop_expert", "experts")])
def test_the_check_passes_sound_weights_and_fails_each_control(rehearsed, control, part):
    """Through ``check_served_path`` itself: the pool serves the faulty
    weights, the reference keeps the sound ones, and the check says not
    correct, by the experts' part at the prefill shape AND in groups of a
    decode step's rows."""
    import numpy as np

    from deeplearning4j_tpu.models.paged_decode import PagedDecodeSlotPool

    ctx, family, cfg, params = rehearsed
    t = ctx.traffic
    served = family.control_params(params, control) if control else params
    pool = PagedDecodeSlotPool(served, cfg, slots=int(t["slots"]),
                               block_T=int(t["block_T"]), max_len=int(t["max_len"]))
    ok = family.check_served_path(ctx, pool, cfg, served, np.random.RandomState(3),
                                  reference_params=params)
    line = ctx.lines[-1]
    assert line["line"] == "check" and line["correct"] == ok == (control is None)
    assert line["slots_live_together"] == len(t["check"]["prompt_lens"]) + len(
        t["check"]["bystander_lens"])
    assert line["decode_row_groups"] >= 2
    for key in ("expert_part_rel_err", "expert_part_rel_err_decode_rows"):
        assert (line[key] > line["expert_rtol"]) == (part == "experts"), (key, line)
