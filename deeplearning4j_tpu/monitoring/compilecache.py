"""Compile-cache hit/miss metrics, attributed per jitted function (ISSUE 12).

jax's persistent compilation cache (enabled by
``common.compile_cache.enable``, placed by ``JAX_COMPILATION_CACHE_DIR`` or
at ``<checkout>/.jax_cache``) emits plain monitoring events:

- ``/jax/compilation_cache/cache_hits`` — an executable was restored from
  disk (``backend_compile`` never ran; the monitor also marks the thread so
  the duration event wrapping the retrieval is not counted as a compile —
  ``tdl_xla_compiles_total`` stays flat across a restart);
- ``/jax/compilation_cache/cache_misses`` — a freshly-compiled executable
  was written to the cache (fires inside the timed compile block, before
  the duration event).

This module turns them into per-fn counters using the same
``note_signature`` thread announcements the RecompileWatchdog claims
(``watchdogs.take_pending_fn`` for hits — nothing will compile, consume it;
``watchdogs.peek_pending_fn`` for misses — the duration event that follows
still needs to claim it for the compile counters). Compiles of helper jits
nobody announced land under ``fn="_unattributed"``, same convention as the
compile counters.

``tdl_compile_cache_bytes`` tracks the on-disk size of the cache directory,
refreshed on every miss (a write changed it) and cheaply on hits.
"""

from __future__ import annotations

import logging
import threading
from typing import Optional

from . import watchdogs
from .registry import MetricsRegistry, get_registry

log = logging.getLogger(__name__)

HIT_EVENT = "/jax/compilation_cache/cache_hits"
MISS_EVENT = "/jax/compilation_cache/cache_misses"

_LOCK = threading.Lock()
_INSTALLED = False
_DIR: Optional[str] = None


def cache_metrics(registry: Optional[MetricsRegistry] = None):
    """Get-or-create the compile-cache metric families."""
    r = registry or get_registry()
    hits = r.counter(
        "tdl_compile_cache_hits_total",
        "Executables restored from the persistent compile cache instead of "
        "recompiling, attributed to the announcing jitted function",
        labels=("fn",))
    misses = r.counter(
        "tdl_compile_cache_misses_total",
        "Freshly-compiled executables written to the persistent compile "
        "cache (first sighting of this program on this cache dir)",
        labels=("fn",))
    size = r.gauge(
        "tdl_compile_cache_bytes",
        "On-disk bytes of the persistent compile cache directory")
    return hits, misses, size


def refresh_bytes() -> int:
    """Re-scan the cache directory into ``tdl_compile_cache_bytes``.
    Called on every miss event (which fires just BEFORE jax writes the new
    entry, so the gauge trails the disk by one entry until the next event)
    and by ``stats()``/scrape-time callers that want it exact."""
    from ..common import compile_cache

    _, _, size = cache_metrics()
    n = compile_cache.cache_size_bytes(_DIR)
    size.set(n)
    return n


_refresh_bytes = refresh_bytes


def _on_event(event: str, **kw) -> None:
    if event == HIT_EVENT:
        # consume the announcement (nothing will compile) and mark the
        # thread so the duration event wrapping this retrieval is NOT
        # counted as a compile (watchdogs._was_cache_restore)
        fn = watchdogs.take_pending_fn() or watchdogs.UNATTRIBUTED
        watchdogs.note_cache_hit()
        hits, _, _ = cache_metrics()
        hits.labels(fn).inc()
    elif event == MISS_EVENT:
        # fires BEFORE the duration event that claims the announcement for
        # the compile counters — peek, don't consume
        fn = watchdogs.peek_pending_fn() or watchdogs.UNATTRIBUTED
        _, misses, _ = cache_metrics()
        misses.labels(fn).inc()
        _refresh_bytes()  # a write just changed the dir size


def install(directory: str) -> None:
    """Install the jax event listener (once) and start announcing
    signatures so hits/misses can be attributed. Called by
    ``common.compile_cache.enable``."""
    global _INSTALLED, _DIR
    with _LOCK:
        _DIR = directory
        # (re-)arm announcements every time: a disable() turned them off
        watchdogs.enable_announcements()
        if _INSTALLED:
            _refresh_bytes()
            return
        import jax

        jax.monitoring.register_event_listener(_on_event)
        watchdogs.enable_announcements()
        cache_metrics()  # declare families up front: /metrics shows zeros
        _refresh_bytes()
        _INSTALLED = True


def stats() -> dict:
    """Point-in-time counters for bench blocks / tests."""
    out = {"dir": _DIR,
           "bytes": refresh_bytes() if _INSTALLED else 0,
           "hits": {}, "misses": {}}
    r = get_registry()
    for key, field in (("tdl_compile_cache_hits_total", "hits"),
                       ("tdl_compile_cache_misses_total", "misses")):
        m = r.get(key)
        if m is None:
            continue
        for s in m.snapshot()["series"]:
            out[field][s["labels"].get("fn", "")] = s["value"]
    return out
