"""Persistent compiled-executable cache (``common.compile_cache``).

Acceptance pins:
- the restart contract: two PROCESSES sharing one JAX_COMPILATION_CACHE_DIR
  — the second pays ZERO per-fn compiles after warmup and shows cache-hit
  counters as evidence;
- per-fn hit/miss attribution through the note_signature thread
  announcements;
- executables restored from disk are NOT counted as compiles (the
  backend_compile duration event wraps jax's cache retrieval too — pinned
  here so a jax upgrade changing that ordering fails loudly);
- the placement contract: with JAX_COMPILATION_CACHE_DIR set nothing in the
  package moves ``jax.config.jax_compilation_cache_dir`` or hands a child
  another directory; unset, parent and children resolve
  ``<checkout>/.jax_cache``;
- warmup completeness satellite: with the cache present the executor warms
  EVERY ParallelInference bucket, not just the smallest.

The suite runs with jax's own switch off (conftest:
JAX_ENABLE_COMPILATION_CACHE=false); the fixture below turns the cache on at
a tmp dir the way an operator would — by configuring jax, not the package.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from deeplearning4j_tpu.common import compile_cache
from deeplearning4j_tpu.common.bucketing import bucket_ladder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_placed(tmp_path):
    """jax configured as if launched with JAX_COMPILATION_CACHE_DIR=<tmp>
    and the cache switched on; everything restored after (the cache is
    process-wide jax config — leaking it would slow and dirty every later
    test)."""
    import jax

    d = str(tmp_path / "compile_cache")
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_enable_compilation_cache", True)
    try:
        yield d
    finally:
        compile_cache.disable()
        jax.config.update("jax_compilation_cache_dir", None)


@pytest.fixture
def enabled_cache(cache_placed):
    assert compile_cache.enable() == cache_placed
    return cache_placed


def _tiny_net():
    from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam

    conf = (NeuralNetConfiguration.Builder().seed(0).updater(Adam(1e-2))
            .list()
            .layer(DenseLayer(n_in=8, n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=4, activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _fit_some(net, steps=3):
    from deeplearning4j_tpu.data.dataset import DataSet

    rs = np.random.RandomState(0)
    X = rs.randn(32, 8).astype(np.float32)
    Y = np.eye(4, dtype=np.float32)[rs.randint(0, 4, 32)]
    for _ in range(steps):
        net._fit_batch(DataSet(X, Y))


# ----------------------------------------------------------- in-process


def test_miss_then_hit_attributed_per_fn(enabled_cache):
    """First compile = miss (written to disk); after dropping jax's
    in-memory caches the same dispatch = hit, both attributed to the
    announcing fit loop — and a restored executable never increments the
    compile counters."""
    import jax

    from deeplearning4j_tpu.monitoring import RecompileWatchdog, compilecache

    net = _tiny_net()
    _fit_some(net)
    s1 = compilecache.stats()
    assert s1["misses"].get("MultiLayerNetwork.train_step") == 1
    assert s1["bytes"] > 0
    assert os.listdir(enabled_cache)  # executables actually on disk

    with RecompileWatchdog() as wd:
        jax.clear_caches()
        net2 = _tiny_net()
        _fit_some(net2)
        s2 = compilecache.stats()
        # every announced executable restored, none compiled. (A couple of
        # anonymous helper jits — threefry seeding etc. — can legitimately
        # get fresh cache keys after an in-process clear_caches; the REAL
        # restart contract, zero misses of any kind in a fresh process, is
        # pinned by test_compiles_flat_across_process_restart below.)
        assert sum(s2["hits"].values()) > sum(s1["hits"].values())
        assert s2["hits"].get("MultiLayerNetwork.train_step", 0) >= 1
        named = {k: v for k, v in wd.stats()["per_fn_compiles"].items()
                 if k != "_unattributed"}
        assert named == {}, (
            f"cache restores must not count as compiles: {named}")


def test_hit_restore_spends_the_watchdog_announcement(enabled_cache):
    """A cache-hit restore must CLEAR the per-watchdog announcement, not
    just skip the compile counters: the restored fn's announcement is spent
    by the restore, so the thread's next UNANNOUNCED compile (an anonymous
    helper jit within the 120s attribution window) stays _unattributed
    instead of minting a phantom tdl_xla_compiles_total{fn=train_step} —
    the exact counter the flat-across-restart acceptance reads."""
    import jax

    from deeplearning4j_tpu.monitoring import RecompileWatchdog

    net = _tiny_net()
    _fit_some(net)  # misses written to disk

    with RecompileWatchdog() as wd:
        jax.clear_caches()
        net2 = _tiny_net()
        _fit_some(net2)  # hit-restores; last announcement = train_step
        # fresh anonymous jit on the SAME thread: a real compile nothing
        # announced
        jax.jit(lambda x: x * 2.0 + 1.0)(np.ones(3, np.float32))
        stats = wd.stats()["per_fn_compiles"]
        assert stats.get("MultiLayerNetwork.train_step", 0) == 0, stats
        assert stats.get("_unattributed", 0) >= 1, stats


def test_cache_bytes_gauge_tracks_directory(enabled_cache):
    from deeplearning4j_tpu.monitoring import get_registry

    from deeplearning4j_tpu.monitoring import compilecache

    net = _tiny_net()
    _fit_some(net, steps=1)
    # the miss event fires just before jax writes the entry, so the gauge
    # trails the disk by one entry until refreshed
    n = compilecache.refresh_bytes()
    g = get_registry().get("tdl_compile_cache_bytes")
    assert g is not None
    assert g.snapshot()["series"][0]["value"] == n
    assert n == compile_cache.cache_size_bytes(enabled_cache) > 0


def test_enable_is_idempotent_and_disable_never_moves_the_dir(cache_placed):
    import jax

    assert compile_cache.enable() == compile_cache.enable() == cache_placed
    assert compile_cache.enabled()
    assert compile_cache.cache_dir() == cache_placed
    compile_cache.disable()
    assert not compile_cache.enabled()
    # off by jax's own switch; the placement is not the package's to touch
    assert jax.config.jax_enable_compilation_cache is False
    assert jax.config.jax_compilation_cache_dir == cache_placed
    assert compile_cache.enable() is None  # stays off until jax says on


# ------------------------------------------------- the restart acceptance


_RESTART_WORKER = textwrap.dedent("""
    import json, os, sys
    import jax
    import numpy as np
    from deeplearning4j_tpu.monitoring import RecompileWatchdog, compilecache
    from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.data.dataset import DataSet

    wd = RecompileWatchdog().install()
    conf = (NeuralNetConfiguration.Builder().seed(0).updater(Adam(1e-2))
            .list()
            .layer(DenseLayer(n_in=8, n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=4, activation="softmax", loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rs = np.random.RandomState(0)
    X = rs.randn(32, 8).astype(np.float32)
    Y = np.eye(4, dtype=np.float32)[rs.randint(0, 4, 32)]
    for _ in range(4):
        net._fit_batch(DataSet(X, Y))
    stats = compilecache.stats()
    print(json.dumps({
        "per_fn_compiles": wd.stats()["per_fn_compiles"],
        "hits": stats["hits"], "misses": stats["misses"],
        "bytes": stats["bytes"], "dir": stats["dir"],
        "config_dir": jax.config.jax_compilation_cache_dir,
    }))
""")


def _child_env(cache_dir=None):
    """A child launched the way an operator would: the cache on, and placed
    by the environment alone (or not placed at all)."""
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="true")
    env.pop(compile_cache.ENV_DIR, None)
    if cache_dir is not None:
        env[compile_cache.ENV_DIR] = cache_dir
    return env


def _run_restart_worker(cache_dir):
    out = subprocess.run(
        [sys.executable, "-c", _RESTART_WORKER], capture_output=True,
        text=True, timeout=240, env=_child_env(cache_dir), cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compiles_flat_across_process_restart(tmp_path):
    """Same JAX_COMPILATION_CACHE_DIR across two processes ⇒ the second
    process records ZERO compiles per fn (every executable —
    the announced train step AND the helper jits — restores from disk),
    with cache-hit counters as the evidence."""
    cache_dir = str(tmp_path / "compile_cache")
    run1 = _run_restart_worker(cache_dir)
    assert run1["per_fn_compiles"].get("MultiLayerNetwork.train_step") == 1
    assert run1["misses"].get("MultiLayerNetwork.train_step") == 1
    assert run1["bytes"] > 0
    # the program used the directory it was given and never moved it
    assert run1["dir"] == run1["config_dir"] == cache_dir

    run2 = _run_restart_worker(cache_dir)
    assert run2["per_fn_compiles"] == {}, (
        f"process restart recompiled: {run2['per_fn_compiles']}")
    assert sum(run2["hits"].values()) > 0
    assert run2["hits"].get("MultiLayerNetwork.train_step", 0) >= 1
    assert run2["misses"] == {}


# ------------------------------------------------- the placement contract


_CONTRACT_WORKER = textwrap.dedent("""
    import json, os, sys
    import jax
    from deeplearning4j_tpu.common import compile_cache
    from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import DenseLayer, OutputLayer
    from deeplearning4j_tpu.parallel.supervisor import GangSupervisor
    from deeplearning4j_tpu.serving import JsonModelServer
    from deeplearning4j_tpu.serving.pool import ReplicaHandle, ServingPool

    work = sys.argv[1]
    seen = {"import": jax.config.jax_compilation_cache_dir}
    out = {"enable": compile_cache.enable()}
    seen["enable"] = jax.config.jax_compilation_cache_dir
    conf = (NeuralNetConfiguration.Builder().seed(0).list()
            .layer(DenseLayer(n_in=4, n_out=4, activation="relu"))
            .layer(OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
            .build())
    JsonModelServer.Builder(MultiLayerNetwork(conf).init()).build()
    seen["server_builder"] = jax.config.jax_compilation_cache_dir
    sup = GangSupervisor("tests.mp_workers:dp_train", n_processes=1,
                         workdir=os.path.join(work, "gang"))
    gang_env = sup._child_env(0, os.path.join(work, "hb"))
    pool = ServingPool("tests/pool_workers.py:stub_server", replicas=1,
                       workdir=os.path.join(work, "pool"))
    pool_env = pool._child_env(ReplicaHandle(id=0))
    seen["children"] = jax.config.jax_compilation_cache_dir
    print(json.dumps({
        **out, "seen": seen,
        "gang_child": gang_env.get(compile_cache.ENV_DIR),
        "pool_child": pool_env.get(compile_cache.ENV_DIR),
        "builder_has_dir_option": hasattr(JsonModelServer.Builder,
                                          "compile_cache_dir"),
    }))
""")


def test_cache_placement_contract(tmp_path):
    """The two halves of the rule, one child process each (run side by side:
    each is mostly interpreter start-up)."""
    placed = str(tmp_path / "placed")
    procs = {
        key: subprocess.Popen(
            [sys.executable, "-c", _CONTRACT_WORKER, str(tmp_path / key)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=_child_env(cache_dir), cwd=REPO)
        for key, cache_dir in (("placed", placed), ("unplaced", None))}
    res = {}
    for key, proc in procs.items():
        out, err = proc.communicate(timeout=240)
        assert proc.returncode == 0, err[-2000:]
        res[key] = json.loads(out.strip().splitlines()[-1])

    # JAX_COMPILATION_CACHE_DIR set: jax reads it itself, and enable(), the
    # serving builder, the gang supervisor and the replica pool neither
    # touch jax.config.jax_compilation_cache_dir nor hand children another
    # directory — children inherit the variable
    r = res["placed"]
    assert r["enable"] == placed
    assert set(r["seen"].values()) == {placed}, r["seen"]
    # the pool builds a replica's whole environment, the supervisor only
    # the extras the launcher lays over os.environ: either way the child
    # sees the variable as this process does
    assert r["pool_child"] == placed
    assert r["gang_child"] is None
    assert not r["builder_has_dir_option"]

    # unset: parent and children all resolve <checkout>/.jax_cache — derived
    # from the package path, never a temp name, pid or time — and no child
    # is handed anything else (it derives the same path from the package)
    expected = os.path.join(REPO, ".jax_cache")
    assert compile_cache.default_dir() == expected
    r = res["unplaced"]
    assert r["seen"]["import"] is None
    assert r["enable"] == expected
    assert r["seen"]["children"] == expected
    assert r["gang_child"] is None and r["pool_child"] is None


def test_multiprocess_cpu_gang_skips_cache(cache_placed, monkeypatch):
    """Reloaded XLA:CPU executables carrying gloo collectives segfault
    (observed: respawned CPU gangs died -11/-6 on their first restored
    step), so the cache is deliberately off on multi-process CPU — TPU gangs
    and single-process runs use it normally."""
    from jax._src import distributed

    monkeypatch.setattr(distributed.global_state, "client", object(),
                        raising=False)
    assert compile_cache.enable() is None
    assert not compile_cache.enabled()


def test_enable_revoked_when_gang_turns_multiprocess(cache_placed,
                                                     monkeypatch):
    """The first net/executor can be built BEFORE jax.distributed
    initializes — the safety probe still answers 'safe' and the cache comes
    on. The next entry point after distributed init must REVOKE it: a
    respawned gang restoring XLA:CPU collective executables from that early
    enable segfaults (-11/-6 at the first restored step)."""
    import jax
    from jax._src import distributed

    monkeypatch.setattr(distributed.global_state, "client", None,
                        raising=False)
    assert compile_cache.enable() == cache_placed  # pre-init
    assert compile_cache.enabled()
    monkeypatch.setattr(distributed.global_state, "client", object(),
                        raising=False)
    assert compile_cache.enable() is None
    assert not compile_cache.enabled()
    assert jax.config.jax_enable_compilation_cache is False
    assert jax.config.jax_compilation_cache_dir == cache_placed


# ------------------------------------------- warmup completeness satellite


def test_executor_warms_every_bucket_with_cache_present(enabled_cache):
    """Satellite: pre-ISSUE-12 only the smallest bucket was warmed and the
    first large coalesced batch ate a compile mid-traffic; with the cache
    enabled the whole ladder is warmed (cheap on cache hit)."""
    from deeplearning4j_tpu.parallel.inference import ParallelInference
    from deeplearning4j_tpu.serving.executor import BatchingInferenceExecutor

    net = _tiny_net()
    pi = ParallelInference(net, batch_limit=16)
    warmed = []
    orig = pi.output_batched
    pi.output_batched = lambda xs: (warmed.append(
        sum(x.shape[0] for x in xs)), orig(xs))[1]
    ex = BatchingInferenceExecutor(
        parallel_inference=pi, max_batch_rows=64,
        warmup_input=np.zeros((1, 8), np.float32)).start()
    try:
        assert ex.wait_warm(120)
        assert warmed == bucket_ladder(64, min_bucket=16,
                                       multiple=pi._ndata)
    finally:
        ex.stop()


def test_executor_warms_smallest_bucket_without_cache():
    """Historical default preserved: no cache, no opt-in ⇒ one warmup
    forward (compiling the whole ladder up front would tax every cold
    start for buckets that may never arrive)."""
    from deeplearning4j_tpu.parallel.inference import ParallelInference
    from deeplearning4j_tpu.serving.executor import BatchingInferenceExecutor

    net = _tiny_net()
    pi = ParallelInference(net, batch_limit=16)
    warmed = []
    orig = pi.output_batched
    pi.output_batched = lambda xs: (warmed.append(
        sum(x.shape[0] for x in xs)), orig(xs))[1]
    ex = BatchingInferenceExecutor(
        parallel_inference=pi, max_batch_rows=64,
        warmup_input=np.zeros((1, 8), np.float32)).start()
    try:
        assert ex.wait_warm(120)
        assert len(warmed) == 1
    finally:
        ex.stop()
