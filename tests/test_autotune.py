"""Persistent Pallas block autotuner (ISSUE 12 tentpole layer 3).

Acceptance pins:
- the interpret-mode search is DETERMINISTIC and lands exactly on the
  static table (measured on a v5e in PR 32, one answer a kernel) at every
  BASELINE.md long-context grid point (exact-match acceptable; regression
  forbidden — on hardware the regression guard keeps a noisy winner from
  displacing the static table);
- ``flash_attention`` consults a persisted measured entry before the
  static defaults, and the result stays numerically correct;
- the table round-trips to disk (atomic write, corruption-tolerant read,
  backend-keyed);
- CI lint: Pallas kernel call sites take block sizes from the registry or
  an explicit argument — never fresh numeric literals (``# block-ok:``
  escapes the static fallback table and the candidate grid).
"""

import ast
import json
import os
import pathlib

import numpy as np
import pytest

from deeplearning4j_tpu.kernels import autotune
from deeplearning4j_tpu.kernels.autotune import (FLASH_CANDIDATES,
                                                 FLASH_KERNELS, AutotuneTable,
                                                 autotune_flash_attention,
                                                 resolve_blocks, shape_key,
                                                 static_flash_blocks)

ROOT = pathlib.Path(__file__).resolve().parent.parent / "deeplearning4j_tpu"


# -------------------------------------------------------------- static table


def _table(Tq, Tk, **kw):
    return [static_flash_blocks(Tq, Tk, kernel=kn, **kw) for kn in FLASH_KERNELS]


def test_static_table_matches_baseline_grid():
    """The table as measured on a v5e in PR 32 (PERF.md section 6), one
    answer for each of flash_fwd, flash_bwd_dkv, flash_bwd_dq."""
    # bert-large.mlm-t512: the whole sequence in one block, all three kernels
    assert _table(512, 512) == [(512, 512)] * 3
    # gpt2-large.train-dp2tp2: the forward whole, the backward with dead
    # blocks to skip
    assert _table(1024, 1024, causal=True) == [(1024, 1024), (512, 512), (512, 512)]
    assert _table(1024, 1024) == [(1024, 1024)] * 3
    # long T: 1024 x 1024, but dKV under a causal mask
    for T in (2048, 4096, 8192, 16384):
        assert _table(T, T) == [(1024, 1024)] * 3
        assert _table(T, T, causal=True) == [(1024, 1024), (512, 512), (1024, 1024)]
    # a block never pads past T rounded up to 128: a 128-token prefill bucket
    # runs 128-wide blocks, and a block divides that length: 640 tokens
    # (5 x 128) run whole or in five
    assert _table(128, 128, causal=True) == [(128, 128)] * 3
    assert _table(100, 100) == [(128, 128)] * 3
    assert _table(640, 640) == [(640, 640)] * 3
    assert _table(640, 640, causal=True) == [(640, 640), (128, 128), (128, 128)]
    assert _table(768, 768) == [(768, 768)] * 3
    assert _table(768, 768, causal=True) == [(768, 768), (384, 384), (384, 384)]
    # mixed: each side by its own length (decode-with-prefix shapes)
    assert _table(128, 8192, causal=True) == [(128, 1024), (128, 512), (128, 1024)]
    # wide heads shrink a block until a grid step fits VMEM
    assert _table(4096, 4096, D=512) == [(1024, 512)] * 3
    # every block the table answers is a candidate of the search
    for T in (512, 1024, 2048, 4096):
        for causal in (False, True):
            assert set(_table(T, T, causal=causal)) <= set(FLASH_CANDIDATES)
    with pytest.raises(ValueError, match="kernel must be one of"):
        static_flash_blocks(512, 512, kernel="bwd")


def test_shape_key_buckets_nearby_shapes_together():
    k1 = shape_key("flash_attention", B=1, H=12, Tq=8000, Tk=8000, D=64,
                   dtype="bfloat16")
    k2 = shape_key("flash_attention", B=1, H=12, Tq=8192, Tk=8192, D=64,
                   dtype="bfloat16")
    assert k1 == k2  # both bucket to tq8192/tk8192
    assert shape_key("flash_attention", B=1, H=12, Tq=8192, Tk=8192, D=64,
                     dtype="float32") != k1  # dtype is part of the key
    assert "d64" in k1 and "bh16" in k1


# ---------------------------------------------------- deterministic search


def test_interpret_search_is_deterministic_static_fallback(tmp_path):
    """ISSUE 12 acceptance (CPU leg): at every BASELINE.md long-context
    grid point the interpret-mode search records that the static table
    stands (timing the Pallas interpreter would persist noise), twice in a
    row, and persists the entry."""
    table = AutotuneTable(str(tmp_path / "autotune_cpu.json"))
    for T in (2048, 4096, 8192, 16384):
        e1 = autotune_flash_attention(1, 12, T, 64, np.float32, table=table,
                                      interpret=True)
        e2 = autotune_flash_attention(1, 12, T, 64, np.float32, table=table,
                                      interpret=True)
        assert e1 == e2
        assert e1["measured"] is False and "block_q" not in e1
    # resolve_blocks now finds a row at every grid point and answers what
    # the static table answers, kernel by kernel — tuned >= static holds by
    # exact match
    reloaded = AutotuneTable(str(tmp_path / "autotune_cpu.json"))
    assert len(reloaded) == 4
    for T in (2048, 4096, 8192, 16384):
        for causal in (False, True):
            for kn in FLASH_KERNELS:
                assert resolve_blocks(
                    "flash_attention", B=1, H=12, Tq=T, Tk=T, D=64,
                    dtype="float32", causal=causal, kernel=kn, table=reloaded
                ) == static_flash_blocks(T, T, causal=causal, kernel=kn)


def test_regression_guard_keeps_static_winner(monkeypatch):
    """A 'winner' measured slower than the static choice must not displace
    it — tuned >= static at every point, by construction. Driven by a fake
    timer keyed on the deterministic order: the candidates [(128, 256),
    (256, 256)], then the call with no block argument (the static table)."""
    import deeplearning4j_tpu.kernels.autotune as mod

    def timer_from(times):
        seq = iter(times)

        def fake(fn, *args, trials, warmup=1):
            return next(seq)

        return fake

    table = AutotuneTable(None)
    # static (last) measures FASTEST → the row carries no block, and lookups
    # keep falling through to the static table
    monkeypatch.setattr(mod, "_time_best_of", timer_from([0.5, 0.5, 0.1]))
    e = autotune_flash_attention(
        1, 2, 256, 64, np.float32, table=table, interpret=False,
        candidates=[(128, 256), (256, 256)], trials=1,
        include_backward=False, persist=False)
    assert "block_q" not in e and e["measured"] is True
    assert e["best_us"] == e["static_us"] == 100000.0
    assert resolve_blocks("flash_attention", B=1, H=2, Tq=256, Tk=256, D=64,
                          dtype="float32", kernel="dq", table=table
                          ) == static_flash_blocks(256, 256, kernel="dq")

    # a candidate beats static → it displaces the static table, for all three
    monkeypatch.setattr(mod, "_time_best_of", timer_from([0.5, 0.1, 0.5]))
    e = autotune_flash_attention(
        1, 2, 256, 64, np.float32, table=table, interpret=False,
        candidates=[(128, 256), (256, 256)], trials=1,
        include_backward=False, persist=False)
    assert (e["block_q"], e["block_k"]) == (256, 256)
    for kn in FLASH_KERNELS:
        assert resolve_blocks("flash_attention", B=1, H=2, Tq=256, Tk=256,
                              D=64, dtype="float32", kernel=kn,
                              table=table) == (256, 256)


def test_all_failed_candidates_raise(tmp_path, monkeypatch):
    """A compiled search in which EVERY candidate fails — the static choice
    included — means the kernel does not run on this device. That must
    raise with the device's error attached, never record a fallback row
    that later lookups would serve as if nothing happened."""
    import deeplearning4j_tpu.kernels.autotune as mod

    def boom(fn, *args, trials, warmup=1):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(mod, "_time_best_of", boom)
    table = AutotuneTable(str(tmp_path / "t.json"))
    with pytest.raises(RuntimeError, match="every flash-attention candidate"
                       ) as ei:
        autotune_flash_attention(
            1, 2, 256, 64, np.float32, table=table, interpret=False,
            candidates=[(128, 256)], trials=1, include_backward=False)
    assert "Mosaic failed" in str(ei.value.__cause__)
    assert len(table) == 0
    assert not os.path.exists(str(tmp_path / "t.json"))


def test_candidate_validity_filters():
    assert autotune.candidate_valid(128, 128, 256, 256, 64)
    assert not autotune.candidate_valid(1024, 1024, 256, 256, 64)  # > T
    # VMEM blowout: giant probs block
    assert not autotune.candidate_valid(2048, 2048, 4096, 4096, 256)
    # what a backward step holds, as Mosaic accepts it ahead of time for a
    # v5e (PR 32): 1024 x 1024 compiles up to D 256 and not at D 512
    assert autotune.candidate_valid(1024, 1024, 4096, 4096, 64)
    assert autotune.candidate_valid(1024, 1024, 4096, 4096, 256)
    assert not autotune.candidate_valid(1024, 1024, 4096, 4096, 512)
    assert autotune.block_vmem_bytes(512, 512, 64, backward=True) > \
        autotune.block_vmem_bytes(512, 512, 64, backward=False)


# --------------------------------------------------------- flash consults


def test_flash_attention_consults_table_and_stays_correct(tmp_path,
                                                          monkeypatch):
    import jax.numpy as jnp

    from deeplearning4j_tpu.kernels import flash_attention, mha_reference
    from deeplearning4j_tpu.monitoring import get_registry

    d = tmp_path / "at"
    monkeypatch.setenv(autotune.ENV_DIR, str(d))
    autotune.reset_table()
    try:
        table = autotune.get_table()
        assert table.path and str(d) in table.path
        # persist a DISTINCTIVE measured winner for this shape bucket
        key = shape_key("flash_attention", B=2, H=2, Tq=64, Tk=64, D=16,
                        dtype="float32")
        table.record(key, {"block_q": 32, "block_k": 32, "measured": True})

        before = _lookup_count("table")
        rs = np.random.RandomState(0)
        q = jnp.asarray(rs.randn(2, 2, 64, 16), jnp.float32)
        k = jnp.asarray(rs.randn(2, 2, 64, 16), jnp.float32)
        v = jnp.asarray(rs.randn(2, 2, 64, 16), jnp.float32)
        out = flash_attention(q, k, v)  # one lookup a kernel
        assert _lookup_count("table") == before + len(FLASH_KERNELS)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(mha_reference(q, k, v)),
                                   atol=2e-5)
        # an explicit argument bypasses the table (no new lookup)
        flash_attention(q, k, v, block_q=16, block_k=16)
        assert _lookup_count("table") == before + len(FLASH_KERNELS)
    finally:
        autotune.reset_table()


def _lookup_count(source):
    from deeplearning4j_tpu.monitoring import get_registry

    m = get_registry().get("tdl_autotune_lookups_total")
    if m is None:
        return 0
    for s in m.snapshot()["series"]:
        if s["labels"] == {"op": "flash_attention", "source": source}:
            return s["value"]
    return 0


# ------------------------------------------------------------- persistence


def test_table_roundtrip_and_corruption_tolerance(tmp_path):
    p = str(tmp_path / "autotune_cpu.json")
    t = AutotuneTable(p, backend="cpu")
    t.record("k1", {"block_q": 512, "block_k": 1024, "measured": True})
    t2 = AutotuneTable(p, backend="cpu")
    assert t2.lookup("k1")["block_q"] == 512
    # wrong backend: measured TPU tiles must never leak onto another backend
    assert AutotuneTable(p, backend="tpu").lookup("k1") is None
    # corruption degrades to empty, never raises
    with open(p, "w") as f:
        f.write("{torn json")
    assert AutotuneTable(p, backend="cpu").lookup("k1") is None
    # missing file is fine
    assert AutotuneTable(str(tmp_path / "nope.json"),
                         backend="cpu").lookup("k1") is None


def test_no_default_table_without_explicit_dir(tmp_path, monkeypatch):
    """Block sizes come from the static table in the source unless
    TDL_AUTOTUNE_DIR is set explicitly: the table never lives beside the
    executable cache, where one run's leftovers would steer the next."""
    monkeypatch.delenv(autotune.ENV_DIR, raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    autotune.reset_table()
    try:
        assert autotune.default_table_path() is None
        assert autotune.get_table().path is None
        assert autotune.resolve_blocks(
            "flash_attention", B=1, H=12, Tq=8192, Tk=8192, D=64,
            dtype="bfloat16") == autotune.static_flash_blocks(8192, 8192)
        monkeypatch.setenv(autotune.ENV_DIR, str(tmp_path / "at"))
        assert autotune.default_table_path().startswith(
            str(tmp_path / "at"))
    finally:
        autotune.reset_table()


# ------------------------------------------------------------------- lint


_BLOCK_KEYWORDS = {"block_q", "block_k"}


def _int_literals(node):
    return [n for n in ast.walk(node)
            if isinstance(n, ast.Constant) and isinstance(n.value, int)
            and not isinstance(n.value, bool)]


def test_no_hardcoded_pallas_block_sizes():
    """ISSUE 12 satellite (repo lint): Pallas kernel call sites in kernels/
    must take block sizes from the autotune registry or an explicit caller
    argument — never fresh numeric literals. The measured static fallback
    table and the candidate grid carry a ``# block-ok: <reason>`` escape.
    Scope: keyword arguments named block_q/block_k and assignments to those
    names whose value embeds an int literal."""
    offenders = []
    for path in sorted((ROOT / "kernels").rglob("*.py")):
        rel = path.relative_to(ROOT.parent).as_posix()
        src = path.read_text()
        lines = src.splitlines()
        tree = ast.parse(src, filename=rel)
        for node in ast.walk(tree):
            hits = []
            if isinstance(node, ast.Call):
                for kw in node.keywords:
                    if kw.arg in _BLOCK_KEYWORDS and _int_literals(kw.value):
                        hits.append(kw.value)
            elif isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                named = any(isinstance(t, ast.Name) and t.id in _BLOCK_KEYWORDS
                            for t in targets)
                if named and node.value is not None and \
                        _int_literals(node.value):
                    hits.append(node.value)
            for h in hits:
                line = lines[h.lineno - 1]
                if "block-ok" not in line and \
                        "block-ok" not in lines[node.lineno - 1]:
                    offenders.append(f"{rel}:{h.lineno}")
    assert not offenders, (
        "hardcoded Pallas block sizes (take them from kernels.autotune, an "
        "explicit argument, or justify with `# block-ok: <reason>`): "
        f"{offenders}")


def test_lint_catches_a_planted_literal(tmp_path):
    """The lint must actually bite: a planted call-site literal without the
    escape is flagged; with the escape it passes."""
    planted = "flash_attention(q, k, v, block_q=256, block_k=512)\n"
    tree = ast.parse(planted)
    call = tree.body[0].value
    flagged = [kw for kw in call.keywords
               if kw.arg in _BLOCK_KEYWORDS and _int_literals(kw.value)]
    assert len(flagged) == 2
