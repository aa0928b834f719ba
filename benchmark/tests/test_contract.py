"""The one-cell command in ``--rehearse`` mode on the CPU: one program per
cell whatever the seed, and the result line's contract. One case per runner
kind; each runs the command twice as subprocesses, with two seeds and one
shared compile-cache directory."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def cells_by_kind():
    """One cell per (runner kind, chips) that BENCHMARK.json holds."""
    seen = {}
    for w in BENCH["workloads"]:
        traffic = json.load(open(os.path.join(ROOT, "benchmark", "traffic",
                                              w["traffic"] + ".json")))
        seen.setdefault((traffic["kind"], w["chips"]), w)
    return [pytest.param(w, id=f"{kind}-{chips}chip")
            for (kind, chips), w in sorted(seen.items())]


def run_cell(cell, seed, cache_dir, *extra, trace=0):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_ENABLE_COMPILATION_CACHE", "XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(cache_dir),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={cell['chips']}")
    return subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", cell["name"],
         "--seed", str(seed), "--seconds", "1", "--trace", str(trace), *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)


def lines(proc):
    return [json.loads(l) for l in proc.stdout.splitlines() if l.startswith("{")]


@pytest.mark.parametrize("cell", cells_by_kind())
def test_second_seed_compiles_nothing_and_the_last_line_is_the_contract(cell, tmp_path):
    first = run_cell(cell, 11, tmp_path, "--rehearse")
    second = run_cell(cell, 3_000_000_019, tmp_path, "--rehearse", trace=1)
    for proc in (first, second):
        assert proc.returncode == 0, proc.stderr[-2000:]
        result = lines(proc)[-1]
        assert set(result) == RESULT_KEYS
        assert result["correct"] is True and result["failed"] == 0
        assert result["attempted"] > 0
        assert result["device"] == {"platform": "cpu", "kind": "cpu",
                                    "count": cell["chips"]}
        sources = {m["name"]: m["source"] for g in ("end_to_end", "per_layer")
                   for m in BENCH[g]}
        for name, m in result["metrics"].items():  # counts only, no device metric
            assert sources[name] == "program_counter"
            assert set(m) == {"value", "unit"}
    setup = {l["line"]: l for l in lines(second) if "line" in l}["setup"]
    assert lines(first)[-2]["cache_misses"] > 0
    assert setup["cache_misses"] == 0 and setup["xla_compiles"] == 0
    assert setup["compiles_in_window"] == 0
    assert set(setup["split_s"]) >= {"import", "weights", "check"}


def test_without_a_tpu_and_without_rehearse_it_fails_with_no_result(tmp_path):
    cell = BENCH["workloads"][0]
    proc = run_cell(cell, 1, tmp_path)
    assert proc.returncode != 0
    assert not [l for l in lines(proc) if "correct" in l]
