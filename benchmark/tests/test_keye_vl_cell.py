"""The keye-vl-2-30b-a3b cell's new files under the contract of
``test_contract.py`` (which rehearses ONE cell a runner kind, the kimi cell
for ``serve_family``): the one-cell command in ``--rehearse`` mode on the CPU,
twice with two seeds and one shared compile-cache directory; the result line's
keys; the new program counters on the traced line; the configuration's file
against the catalog's keys; and a control run that ends after the check."""

import json
import os

from test_contract import BENCH, RESULT_KEYS, ROOT, lines, run_cell

CELL = next(w for w in BENCH["workloads"] if w["name"] == "keye-vl-2-30b-a3b.longdoc-qa")


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
    # counts only in a rehearsal: the selection's share and the expert layer's
    assert 0 < metrics["dsa.selected_row_share"]["value"] < 100
    assert 0 < metrics["moe.experts_touched_share"]["value"] <= 100
    assert metrics["kv.cache_bytes_per_token"]["value"] == 2 * (32 + 32 + 128) * 4
    assert "step.mfu.decode.dsa" not in metrics  # a device metric: never on the CPU


def test_a_control_run_ends_after_the_check_which_says_not_correct(tmp_path):
    from benchmark.runners.serve_family import CONTROL_ENV

    os.environ[CONTROL_ENV] = "stale_index_keys"
    try:
        proc = run_cell(CELL, 5, tmp_path, "--rehearse")
    finally:
        del os.environ[CONTROL_ENV]
    assert proc.returncode == 0, proc.stderr[-2000:]  # 0: the check caught it
    last = lines(proc)[-1]
    assert last == {"line": "control", "control": "stale_index_keys", "correct": False}


def test_the_configuration_file_holds_every_published_key():
    """The catalog's row (model-configs guide), key for key: only the depth
    differs, and it is listed."""
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 262144, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "KeyeVL2", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4,
        "num_local_experts": 128, "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                         "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    entry = next(c for c in BENCH["configs"] if c["name"] == CELL["config"])
    config = json.load(open(os.path.join(ROOT, entry["file"])))
    differs = [k for k, v in published.items() if config.get(k, "absent") != v]
    assert differs == entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert config["published"]["num_hidden_layers"] == 48
    for key in ("assumed", "departures", "deployment", "reduced_why", "rehearse"):
        assert config[key]
    traffic = json.load(open(os.path.join(ROOT, "benchmark", "traffic",
                                          CELL["traffic"] + ".json")))
    assert (traffic["slots"], traffic["block_T"], traffic["max_len"]) == (16, 32, 17408)
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 8192,
                                        "sigma": 0.5, "min": 4096, "max": 16384}
    assert traffic["answer_tokens"] == {"dist": "lognormal", "median": 128,
                                        "sigma": 0.4, "min": 64, "max": 384}
