"""Persistent compiled-executable cache: one rule for where it lives.

- ``JAX_COMPILATION_CACHE_DIR`` set → jax reads it itself at import; this
  module never writes ``jax_compilation_cache_dir``, and child processes
  inherit the variable. Whoever launches the program places the cache.
- not set → ``<checkout>/.jax_cache`` (derived from the package path, in
  ``.gitignore``): the same directory for a parent and every child it
  spawns, on every run. The directory is part of jax's cache key, so a
  path that moves (a temp dir, a pid, a timestamp) never hits.

``enable()`` is idempotent and cheap; every entry point that is about to
build an executable (fit loops, executors, trainers, servers) calls it. The
first call installs the hit/miss metrics listener
(``monitoring.compilecache``), so ``tdl_compile_cache_{hits,misses}_total``
are attributed per-fn through the same ``note_signature`` announcements the
recompile watchdog uses. On a cache hit jax returns the deserialized
executable before ``backend_compile`` runs, so ``tdl_xla_compiles_total``
stays flat across a restart (pinned by tests/test_compile_cache.py).

Off switches are jax's own: ``JAX_ENABLE_COMPILATION_CACHE=false`` (the test
suite runs with it so no state outside git steers a test), and
:func:`disable`, which flips the same flag. Multi-process CPU (gloo) gangs
are always excluded — see :func:`_unsafe_multiprocess_cpu`.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Optional

log = logging.getLogger(__name__)

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"

_lock = threading.Lock()
_enabled_dir: Optional[str] = None
_gang_skip_logged = False


def default_dir() -> str:
    """``<checkout>/.jax_cache`` — beside the package, wherever it sits."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def enable() -> Optional[str]:
    """Turn the persistent cache on — where ``JAX_COMPILATION_CACHE_DIR``
    says, else at :func:`default_dir` — and install the metrics listener.
    Returns the directory in use, or None when the cache is off for this
    process (jax's own switch, or a multi-process CPU gang). Re-probed on
    every call: the first net/executor can be built before
    ``jax.distributed`` initializes, and an early enable must be revoked
    once the process turns out to be a CPU gang rank."""
    global _enabled_dir, _gang_skip_logged
    import jax

    if not jax.config.jax_enable_compilation_cache:
        _enabled_dir = None
        return None
    if _unsafe_multiprocess_cpu():
        if not _gang_skip_logged:
            log.info("compile cache: off on a multi-process CPU gang "
                     "(reloaded XLA:CPU collective executables are not "
                     "crash-safe); TPU gangs and single-process runs use it")
            _gang_skip_logged = True
        disable()
        return None
    with _lock:
        directory = jax.config.jax_compilation_cache_dir
        if _enabled_dir is not None and _enabled_dir == directory:
            return directory
        if directory is None:
            # only reachable with JAX_COMPILATION_CACHE_DIR unset: jax seeds
            # this config from the variable at import
            directory = default_dir()
            jax.config.update("jax_compilation_cache_dir", directory)
        os.makedirs(directory, exist_ok=True)
        # cache EVERY executable: the default thresholds (1s compile time,
        # non-zero entry size) would silently skip exactly the small steady
        # executables whose recompile-on-restart churn this kills
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        # jax memoizes its is-cache-used decision on the FIRST compile of
        # the process; enabling after any earlier compile would be a silent
        # no-op without this reset
        from jax.experimental.compilation_cache import compilation_cache

        compilation_cache.reset_cache()
        _enabled_dir = directory
    from ..monitoring import compilecache

    compilecache.install(directory)
    return directory


def _unsafe_multiprocess_cpu() -> bool:
    """True on a multi-process CPU (gloo) gang: deserialized XLA:CPU
    executables carrying cross-process collectives crash on reload
    (observed: respawned CPU gangs die SIGSEGV/SIGABRT on their first
    restored step). The cache stays on for TPU gangs — serialized TPU
    executables are the cache's designed-for case — and for every
    single-process path, CPU included. Probed WITHOUT initializing the
    backend (env/config only): this runs from constructors that may
    execute before a worker's first computation."""
    try:
        import jax
        from jax._src import distributed

        if distributed.global_state.client is None:
            return False
        plats = (jax.config.jax_platforms
                 or os.environ.get("JAX_PLATFORMS") or "")
        return plats.split(",")[0].strip().lower() == "cpu"
    except Exception:
        return False


def disable() -> None:
    """Stop reading and writing the persistent cache in this process, by
    jax's own switch — the directory setting is never touched. Stays off
    until ``jax_enable_compilation_cache`` is set again (tests do; nothing
    in the package does)."""
    global _enabled_dir
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    with _lock:
        if not jax.config.jax_enable_compilation_cache:
            return
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        _enabled_dir = None
    from ..monitoring import watchdogs

    watchdogs.disable_announcements()


def cache_dir() -> Optional[str]:
    """The enabled cache directory, or None."""
    return _enabled_dir


def enabled() -> bool:
    return _enabled_dir is not None


def cache_size_bytes(directory: Optional[str] = None) -> int:
    """Total bytes of serialized executables on disk (the
    ``tdl_compile_cache_bytes`` gauge's source)."""
    directory = directory or _enabled_dir
    if not directory:
        return 0
    total = 0
    try:
        with os.scandir(directory) as it:
            for entry in it:
                try:
                    if entry.is_file(follow_symlinks=False):
                        total += entry.stat(follow_symlinks=False).st_size
                except OSError:
                    continue
    except OSError:
        return 0
    return total
