"""The trinity-large-preview cell's new files under the contract of
``test_contract.py`` (which rehearses ONE cell a runner kind, the kimi cell for
``serve_family``): the one-cell command in ``--rehearse`` mode on the CPU, twice
with two seeds and one shared compile-cache directory; the result line's keys;
the new program counters on the traced line; and a control run that ends
after the check."""

import os

from test_contract import BENCH, RESULT_KEYS, lines, run_cell

CELL = next(w for w in BENCH["workloads"] if w["name"] == "trinity-large-preview.mixed-len")


def test_second_seed_compiles_nothing_and_the_traced_line_holds_the_new_counters(tmp_path):
    first = run_cell(CELL, 11, tmp_path, "--rehearse")
    second = run_cell(CELL, 3_000_000_019, tmp_path, "--rehearse", trace=1)
    for proc in (first, second):
        assert proc.returncode == 0, proc.stderr[-2000:]
        result = lines(proc)[-1]
        assert set(result) == RESULT_KEYS
        assert result["correct"] is True and result["failed"] == 0
        assert result["attempted"] > 0
        assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    setup = {l["line"]: l for l in lines(second) if "line" in l}["setup"]
    assert lines(first)[-2]["cache_misses"] > 0
    assert setup["cache_misses"] == 0 and setup["xla_compiles"] == 0
    assert setup["compiles_in_window"] == 0
    metrics = lines(second)[-1]["metrics"]
    # counts only in a rehearsal: the window's share of the rows and the experts'
    assert 20 < metrics["swa.visible_row_share"]["value"] <= 100
    assert 0 < metrics["moe.experts_touched_share"]["value"] <= 100
    assert metrics["kv.cache_bytes_per_token"]["value"] == 5 * 2 * 32 * 4
    assert "step.mfu.decode.swa" not in metrics  # a device metric: never on the CPU


def test_a_control_run_ends_after_the_check_which_says_not_correct(tmp_path):
    from benchmark.runners.serve_family import CONTROL_ENV

    os.environ[CONTROL_ENV] = "read_freed_block"
    try:
        proc = run_cell(CELL, 5, tmp_path, "--rehearse")
    finally:
        del os.environ[CONTROL_ENV]
    assert proc.returncode == 0, proc.stderr[-2000:]  # 0: the check caught it
    assert lines(proc)[-1] == {"line": "control", "control": "read_freed_block",
                               "correct": False}
