"""Multi-process launcher + cross-process collectives (the control plane).

Reference parity (SURVEY §2.6 J18, §3.4, §5.8): the reference crosses process
boundaries with Spark task shipping for control and an Aeron UDP mesh rooted
by ``ModelParameterServer``/``MeshOrganizer`` for the data plane. The
TPU-native control plane is the PJRT distributed runtime:
``jax.distributed.initialize`` against a process-0 coordinator, after which
every process sees the GLOBAL device set and compiled steps carry XLA
collectives across the process boundary (ICI/DCN on hardware, gloo on the
CPU dev box).

Three pieces:

- :func:`initialize` — one-call worker-side init. On CPU it applies the full
  dev-box recipe (force N host devices, pin the platform, gloo cross-process
  collectives) so 2+ process tests run on any machine: the analog of the
  reference's ``local[N]`` Spark tests and the
  ``--xla_force_host_platform_device_count`` single-process fake
  (SURVEY §4.4).
- :class:`ProcessCollectives` — the host-side ``Collectives`` SPI over REAL
  process boundaries (pickled blobs over the jax allgather data plane);
  drop-in where tests previously used ``FakeCollectives``.
- :func:`launch` — parent-side subprocess spawner: starts N workers running
  ``module:function`` targets, waits, returns per-rank results. Used by the
  2-process pytest tier and ``__graft_entry__.dryrun_multichip``.
"""

from __future__ import annotations

import logging
import os
import pickle
import re
import socket
import subprocess
import sys
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from .collectives import Collectives

log = logging.getLogger(__name__)

_ENV_COORD = "TDL_COORDINATOR"
_ENV_NPROC = "TDL_NUM_PROCESSES"
_ENV_PID = "TDL_PROCESS_ID"
_ENV_LOCAL = "TDL_LOCAL_DEVICES"
_ENV_PLATFORM = "TDL_PLATFORM"


def free_port() -> int:
    """Best-effort free port. Inherently TOCTOU: the socket closes before the
    coordinator binds, so a concurrent process can steal the port in the gap —
    callers must treat a coordinator bind failure as retryable
    (:func:`launch` and ``GangSupervisor`` respawn on a fresh port)."""
    with socket.socket() as s:  # timeout-ok: bind-only probe, no network I/O
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# stderr signatures of a coordinator that lost the free_port() race (gRPC
# server bind) — "respawn the gang on a fresh port". Deliberately NOT the
# sibling-side symptoms (connect/barrier timeouts): those also fire when a
# rank dies for unrelated reasons, and a true port race always surfaces the
# bind error on the coordinator rank itself.
_BIND_FAILURE_RE = re.compile(
    r"address already in use|failed to bind|bind address|"
    r"could not start .*coordin",
    re.IGNORECASE)


def coordinator_bind_failed(results: Sequence["WorkerResult"]) -> bool:
    """True when a gang's failure pattern matches the free_port() TOCTOU:
    rank 0 (the process hosting the coordination service) exited nonzero
    with a bind signature on stderr. Only rank 0 counts — a sibling rank
    failing with its own bind-ish message (e.g. a worker-local HTTP server
    on a busy port) is a real worker error, and re-running the whole gang
    on it would re-execute worker side effects just to hit it again."""
    return any(r.rank == 0 and r.returncode != 0
               and _BIND_FAILURE_RE.search(r.stderr or "")
               for r in results)


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_devices: Optional[int] = None,
    platform: Optional[str] = None,
) -> None:
    """Initialize this process as rank ``process_id`` of a distributed run.

    Args default from the TDL_* env vars :func:`launch` sets, so a worker
    target can just call ``initialize()``. Must run before the first real
    use of jax devices in the process.
    """
    coordinator_address = coordinator_address or os.environ[_ENV_COORD]
    num_processes = int(num_processes or os.environ[_ENV_NPROC])
    process_id = int(process_id if process_id is not None else os.environ[_ENV_PID])
    local_devices = int(local_devices or os.environ.get(_ENV_LOCAL, "0")) or None
    platform = platform or os.environ.get(_ENV_PLATFORM) or None

    if platform == "cpu" and local_devices:
        # must precede CPU client creation; harmless if jax already imported
        # as long as no backend has initialized yet. Replace (not append) any
        # inherited force-count flag — pytest parents export =8 via conftest.
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(f"--xla_force_host_platform_device_count={local_devices}")
        os.environ["XLA_FLAGS"] = " ".join(flags)

    import jax

    if platform:
        # jax reads JAX_PLATFORMS once, at import, and importing this package
        # already imported jax — a platform passed here must go to the config
        jax.config.update("jax_platforms", platform)
    if platform == "cpu":
        # cross-process collectives for the CPU client ride gloo
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


class ProcessCollectives(Collectives):
    """Host-side control-plane SPI over real process boundaries.

    Arbitrary pickleable blobs ride the jax cross-process allgather (gloo on
    CPU, DCN on pods) as padded uint8 tensors: one small round for lengths,
    one for payloads. This is the production counterpart of
    ``FakeCollectives`` — same SPI, genuine process boundary — and the
    transport ``EncodedGradientsAccumulator.exchange`` uses for the DCN
    gradient-sharing mode (reference: Aeron ``NDArrayMessage`` chunking,
    SURVEY §5.8).
    """

    def __init__(self):
        import jax

        self.rank = jax.process_index()
        self.world = jax.process_count()

    def _allgather_arrays(self, value: np.ndarray) -> np.ndarray:
        from jax.experimental.multihost_utils import process_allgather

        return np.asarray(process_allgather(value))

    def allgather(self, name: str, value: Any) -> List[Any]:
        blob = np.frombuffer(pickle.dumps(value), np.uint8)
        lens = self._allgather_arrays(np.asarray([blob.size], np.int64))
        lens = lens.reshape(self.world)
        padded = np.zeros(int(lens.max()), np.uint8)
        padded[: blob.size] = blob
        data = self._allgather_arrays(padded).reshape(self.world, -1)
        return [
            pickle.loads(data[i, : int(lens[i])].tobytes()) for i in range(self.world)
        ]

    def broadcast(self, name: str, value: Any, root: int = 0) -> Any:
        return self.allgather(name, value)[root]

    def gather(self, name: str, value: Any, root: int = 0):
        vals = self.allgather(name, value)
        return vals if self.rank == root else None

    def barrier(self, name: str) -> None:
        from jax.experimental.multihost_utils import sync_global_devices

        sync_global_devices(name)


@dataclass
class WorkerResult:
    rank: int
    returncode: int
    stdout: str
    stderr: str


def launch(
    target: str,
    n_processes: int,
    n_local_devices: int = 2,
    platform: str = "cpu",
    timeout: float = 600.0,
    extra_env: Optional[Dict[str, str]] = None,
    args: Sequence[str] = (),
    cwd: Optional[str] = None,
    port_attempts: int = 3,
) -> List[WorkerResult]:
    """Spawn ``n_processes`` workers each running ``module:function``.

    The worker entry (this module's ``__main__``) calls :func:`initialize`
    from the TDL_* env and then the target function (no arguments; it reads
    ``sys.argv``/env for parameters). Returns once every worker exits.

    A gang that dies with a coordinator bind/connect failure (the
    ``free_port`` TOCTOU) is respawned on a fresh port up to
    ``port_attempts`` times before the failing results are returned.
    """
    for attempt in range(max(1, port_attempts)):
        procs = spawn(target, n_processes, n_local_devices, platform,
                      extra_env, args, cwd)
        results = wait(procs, timeout=timeout, abort_on_failure=True)
        if not coordinator_bind_failed(results) or attempt == port_attempts - 1:
            return results
        log.warning("coordinator bind failure (port race); respawning gang "
                    "on a fresh port (attempt %d/%d)", attempt + 2, port_attempts)
    return results


def check_platform(platform: str, n_processes: int) -> None:
    """Refuse a multi-process gang on the local TPU. A chip belongs to one
    process at a time and nothing here pins a rank to its own chip: rank 0
    would take every local chip and the other ranks would fail or hang at
    backend start-up."""
    if platform == "tpu" and n_processes > 1:
        raise ValueError(
            f"platform='tpu' with n_processes={n_processes}: no per-rank "
            "chip pinning on one host — drive all local chips from ONE "
            "process (n_processes=1), or run the gang on platform='cpu'")


def spawn(
    target: str,
    n_processes: int,
    n_local_devices: int = 2,
    platform: str = "cpu",
    extra_env: Optional[Dict[str, str]] = None,
    args: Sequence[str] = (),
    cwd: Optional[str] = None,
    port: Optional[int] = None,
    log_dir: Optional[str] = None,
) -> List[subprocess.Popen]:
    """Start the worker processes and return the live Popen handles (the
    kill-one-process tests need the handles mid-flight).

    With ``log_dir`` set, worker stdout/stderr go to ``rank{r}.out/.err``
    files instead of pipes — required by long-lived monitors (the gang
    supervisor) that must not drain pipes continuously: an undrained 64KB
    pipe buffer would block a chatty worker mid-training and masquerade as a
    hang."""
    check_platform(platform, n_processes)
    port = port or free_port()
    procs = []
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
    for rank in range(n_processes):
        env = dict(os.environ)
        env.update(extra_env or {})
        env[_ENV_COORD] = f"127.0.0.1:{port}"
        env[_ENV_NPROC] = str(n_processes)
        env[_ENV_PID] = str(rank)
        env[_ENV_LOCAL] = str(n_local_devices)
        env[_ENV_PLATFORM] = platform
        if platform:
            # the child's jax must never open a backend the parent did not
            # name: a CPU child that probes libtpu takes (or waits on) the
            # chip its parent holds
            env["JAX_PLATFORMS"] = platform
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        if log_dir:
            stdout = open(os.path.join(log_dir, f"rank{rank}.out"), "w")
            stderr = open(os.path.join(log_dir, f"rank{rank}.err"), "w")
        else:
            stdout = stderr = subprocess.PIPE
        proc = subprocess.Popen(
            [sys.executable, "-m", "deeplearning4j_tpu.parallel.launcher", target, *args],
            env=env,
            stdout=stdout,
            stderr=stderr,
            text=True,
            cwd=cwd or repo_root,
        )
        if log_dir:
            stdout.close()  # the child holds the fd now
            stderr.close()
            proc.tdl_log_paths = (stdout.name, stderr.name)
        procs.append(proc)
    return procs


def wait(procs: List[subprocess.Popen], timeout: float = 600.0,
         abort_on_failure: bool = False) -> List[WorkerResult]:
    # drain every pipe CONCURRENTLY: a later rank filling its pipe buffer
    # while an earlier rank blocks in a collective would otherwise deadlock
    # the gang until the timeout kill
    import threading

    results: List[Optional[WorkerResult]] = [None] * len(procs)
    stop = threading.Event()

    def drain(rank: int, p: subprocess.Popen):
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            out, err = p.communicate()
            err = (err or "") + "\n[launcher] killed after timeout"
        results[rank] = WorkerResult(rank, p.returncode, out or "", err or "")

    def abort_watch():
        # synchronous SPMD cannot survive a lost member: once any rank dies
        # nonzero, the survivors are stuck in collectives/connects — kill
        # them after a short grace instead of burning the full gang timeout
        while not stop.wait(0.25):
            codes = [p.poll() for p in procs]
            if any(c not in (None, 0) for c in codes):
                stop.wait(5.0)  # grace: let siblings fail on their own terms
                for p in procs:
                    if p.poll() is None:
                        p.kill()
                return

    threads = [threading.Thread(target=drain, args=(i, p), daemon=True)
               for i, p in enumerate(procs)]
    if abort_on_failure:
        threads.append(threading.Thread(target=abort_watch, daemon=True))
    for t in threads:
        t.start()
    for t in threads[:len(procs)]:
        t.join(timeout + 30)
    stop.set()
    return [r if r is not None else WorkerResult(i, -1, "", "[launcher] no result")
            for i, r in enumerate(results)]


def _worker_main(argv: Sequence[str]) -> None:
    target = argv[0]
    mod_name, _, fn_name = target.rpartition(":")
    initialize()
    if mod_name.endswith(".py"):  # file target: /path/to/workers.py:fn
        import importlib.util

        spec = importlib.util.spec_from_file_location("_tdl_mp_target", mod_name)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    else:
        import importlib

        mod = importlib.import_module(mod_name)
    getattr(mod, fn_name)()


if __name__ == "__main__":  # worker entry: python -m ...launcher mod:fn [args]
    _worker_main(sys.argv[1:])
