"""Test bootstrap: force an 8-device virtual CPU mesh BEFORE jax import.

SURVEY.md §4.6 #5: `XLA_FLAGS=--xla_force_host_platform_device_count=8` +
`JAX_PLATFORMS=cpu` is the TPU-world analog of DL4J's `local[N]` Spark tests —
multi-device semantics with zero real chips. Must run before anything imports
jax, which pytest guarantees for conftest at collection start.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TDL_DEFAULT_FLOAT", "float32")
# numerics tests (grad checks, parity-to-1e-6 assertions) run the fp32 policy;
# the bf16 AMP path has its own dedicated tests (tests/test_precision.py)
os.environ.setdefault("TDL_MATMUL_PRECISION", "float32")

# No state outside git steers a test: the persistent compile cache
# (common.compile_cache, default <checkout>/.jax_cache) is off for this
# process AND every child it spawns, by jax's own switch. The cache's own
# tests turn it back on against a tmp_path.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import time

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seeded_rng():
    """Deterministic global RNG per test (BaseNd4jTest seeds Nd4j RNG)."""
    from deeplearning4j_tpu.rng import set_seed

    set_seed(12345)
    np.random.seed(12345)
    yield


_SHM_DIR = "/dev/shm"


def _descends_from_me(pid: int) -> bool:
    """Whether ``pid`` is this process or one it started (walks /proc). A
    creator that is gone is nobody else's: count it here."""
    me = os.getpid()
    while pid > 0:
        if pid == me:
            return True
        try:
            with open(f"/proc/{pid}/stat") as f:
                pid = int(f.read().rsplit(")", 1)[1].split()[1])  # ppid
        except (OSError, ValueError, IndexError):
            return True
    return False


def _tdl_shm_segments():
    """This process's ``tdl_*`` segments. /dev/shm is shared by the xdist
    workers, and a segment carries its creator's pid (``tdl_etl_<pid>_<id>``):
    one that a LIVE process outside this worker's tree made is another
    worker's test in flight, not this test's leak."""
    try:
        names = {n for n in os.listdir(_SHM_DIR) if n.startswith("tdl_")}
    except OSError:  # non-Linux: no visible shm namespace to audit
        return set()
    mine = set()
    for n in names:
        pid = n.split("_")[2] if n.count("_") >= 3 else ""
        if not pid.isdigit() or _descends_from_me(int(pid)):
            mine.add(n)
    return mine


@pytest.fixture(autouse=True)
def _no_leaked_children_or_shm():
    """ISSUE 6 satellite: fail any test that leaves live child processes
    (multiprocessing workers — e.g. an ETL service that wasn't closed) or
    shared-memory segments behind. Leaks are cleaned up after the failure is
    recorded so one offender can't cascade into the rest of the suite."""
    import multiprocessing as mp

    before = _tdl_shm_segments()
    yield
    leaked_procs = []
    children = mp.active_children()  # also reaps finished children
    if children:
        deadline = time.monotonic() + 3.0  # grace: normal teardown in flight
        for p in children:
            p.join(timeout=max(0.0, deadline - time.monotonic()))
        leaked_procs = [p.name for p in children if p.is_alive()]
        for p in children:
            if p.is_alive():
                p.terminate()
                p.join(timeout=2.0)
            if p.is_alive():
                p.kill()
                p.join(timeout=2.0)
    leaked_shm = _tdl_shm_segments() - before
    for name in leaked_shm:  # unlink so later tests start clean
        try:
            os.unlink(os.path.join(_SHM_DIR, name))
        except OSError:  # already gone: the owner raced our cleanup
            pass
    assert not leaked_procs and not leaked_shm, (
        f"test leaked live child processes {leaked_procs} and/or "
        f"shared-memory segments {sorted(leaked_shm)} — close() the ETL "
        "service / iterator (fit loops do it in their finally)")


# -- observability-artifact leak audit (ISSUE 7 satellite) --------------------

# Filenames/dirnames the observability plane writes. A test that points
# TDL_METRICS_SPOOL_DIR / TDL_FLIGHT_DIR (or a GangSupervisor workdir) at
# cwd or the shared tempdir instead of tmp_path leaves these behind for
# every later test (and CI run) to trip over.
_OBS_ARTIFACT_PREFIXES = ("tdl_metrics_", "tdl_flight_", "tdl_history_",
                          "tdl_gang_")
_OBS_ARTIFACT_NAMES = ("postmortem.json",)


def _obs_artifacts():
    import tempfile

    found = set()
    for base in (os.getcwd(), tempfile.gettempdir()):
        try:
            names = os.listdir(base)
        except OSError:
            continue
        for n in names:
            if n.startswith(_OBS_ARTIFACT_PREFIXES) or n in _OBS_ARTIFACT_NAMES:
                found.add(os.path.join(base, n))
    return found


@pytest.fixture(autouse=True)
def _no_spool_or_postmortem_outside_tmp_path():
    """Fail any test that leaves metrics-spool / flight-recorder / postmortem
    files (or a default-workdir gang dir) outside its tmp_path. Leaks are
    cleaned after the failure is recorded so one offender can't cascade."""
    import shutil

    before = _obs_artifacts()
    yield
    leaked = _obs_artifacts() - before
    for path in leaked:  # clean so later tests start from a known state
        try:
            if os.path.isdir(path):
                shutil.rmtree(path, ignore_errors=True)
            else:
                os.unlink(path)
        except OSError:
            pass
    assert not leaked, (
        f"test leaked observability artifacts outside tmp_path: "
        f"{sorted(leaked)} — point TDL_METRICS_SPOOL_DIR/TDL_FLIGHT_DIR and "
        "GangSupervisor(workdir=...) at tmp_path")
