"""Device-memory and XLA-recompilation watchdogs.

Two failure modes dominate real TPU training and are invisible in the
reference's listener stack:

- **HBM creep / OOM**: XLA owns device memory; by the time an allocation
  fails the job is dead. :class:`DeviceMemoryWatchdog` samples
  ``device.memory_stats()`` into in-use / high-water gauges (host-RSS
  fallback on backends that expose no stats, e.g. CPU smoke runs) and can
  dump a live-buffer summary when a threshold is crossed — the moral
  equivalent of ``common.debug.LiveBufferMonitor`` wired into metrics.

- **silent recompilation**: a shape-churning input pipeline recompiles the
  step executable every few minibatches and the job quietly runs 10-100x
  slow. :class:`RecompileWatchdog` hooks ``jax.monitoring``'s
  backend-compile event for counts + compile seconds, and correlates our
  own per-function call signatures (noted by the fit loops) to warn when
  the SAME function compiles ≥ N times within M steps.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from collections import OrderedDict, defaultdict, deque
from typing import Dict, List, Optional, Tuple

from . import flight
from .registry import MetricsRegistry, get_registry

logger = logging.getLogger("deeplearning4j_tpu.monitoring")


def host_rss_bytes() -> int:
    """Current resident set size of this process, in bytes."""
    try:  # /proc gives CURRENT rss; getrusage only gives the peak
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except Exception:
        import resource
        import sys

        # ru_maxrss is KB on Linux but BYTES on macOS (the only platform
        # that actually reaches this fallback — no /proc there)
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return rss if sys.platform == "darwin" else rss * 1024


class DeviceMemoryWatchdog:
    """Watermark sampler over ``jax.devices()`` memory stats.

    ``sample()`` is explicit (cheap, host-side only); ``start(interval)``
    runs it on a daemon thread for long jobs. The high-water gauge is OURS
    (max over samples), so it works even on backends whose stats carry no
    peak field — and on the host-RSS fallback.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 threshold_bytes: Optional[int] = None,
                 dump_live_buffers: bool = False, dump_top: int = 10):
        self.registry = registry or get_registry()
        self.threshold_bytes = threshold_bytes
        self.dump_live_buffers = dump_live_buffers
        self.dump_top = dump_top
        r = self.registry
        self._in_use = r.gauge(
            "tdl_device_memory_bytes_in_use",
            "Device memory currently allocated (host RSS on statless backends)",
            labels=("device",))
        self._high_water = r.gauge(
            "tdl_device_memory_high_water_bytes",
            "High-water mark of device memory in use since watchdog creation",
            labels=("device",))
        self._limit = r.gauge(
            "tdl_device_memory_limit_bytes",
            "Device memory capacity where the backend reports it",
            labels=("device",))
        self._exceeded = r.counter(
            "tdl_device_memory_threshold_exceeded_total",
            "Samples that found memory in use above the configured threshold")
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    def sample(self) -> Dict[str, int]:
        """One sampling pass; returns {device_label: bytes_in_use}."""
        import jax

        out: Dict[str, int] = {}
        saw_stats = False
        for d in jax.devices():
            stats = None
            try:
                stats = d.memory_stats()
            except Exception:  # backend without the API at all
                stats = None
            if not stats:
                continue
            saw_stats = True
            label = f"{d.platform}:{d.id}"
            in_use = int(stats.get("bytes_in_use", 0))
            out[label] = in_use
            self._in_use.labels(label).set(in_use)
            self._high_water.labels(label).set_to_max(
                max(in_use, int(stats.get("peak_bytes_in_use", 0))))
            limit = stats.get("bytes_limit")
            if limit:
                self._limit.labels(label).set(int(limit))
        if not saw_stats:
            # the CPU backend exposes no per-device stats;
            # host RSS is the best available proxy for the smoke tier
            rss = host_rss_bytes()
            out["host"] = rss
            self._in_use.labels("host").set(rss)
            self._high_water.labels("host").set_to_max(rss)
        self._check_threshold(out)
        return out

    def _check_threshold(self, sampled: Dict[str, int]) -> None:
        if self.threshold_bytes is None:
            return
        over = {k: v for k, v in sampled.items() if v > self.threshold_bytes}
        if not over:
            return
        self._exceeded.inc()
        worst = max(over, key=over.get)
        logger.warning(
            "device memory watchdog: %s at %.1f MB exceeds threshold %.1f MB",
            worst, over[worst] / 1e6, self.threshold_bytes / 1e6)
        if self.dump_live_buffers:
            for line in self.live_buffer_summary(self.dump_top):
                logger.warning("  %s", line)

    def live_buffer_summary(self, top: int = 10) -> List[str]:
        """Largest live device buffers grouped by (shape, dtype) — the
        'what is actually holding HBM' dump."""
        import jax

        groups: Dict[Tuple[str, str], List[int]] = defaultdict(list)
        for a in jax.live_arrays():
            try:
                groups[(str(a.shape), str(a.dtype))].append(a.nbytes)
            except Exception:
                continue
        rows = sorted(((sum(v), len(v), k) for k, v in groups.items()),
                      reverse=True)[:top]
        return [f"{total / 1e6:9.2f} MB x{count:<5} {shape} {dtype}"
                for total, count, (shape, dtype) in rows]

    # -- background sampling ----------------------------------------------

    def start(self, interval_s: float = 10.0) -> "DeviceMemoryWatchdog":
        if self._thread is not None:
            return self
        self._stop.clear()

        def loop():
            while not self._stop.wait(interval_s):
                try:
                    self.sample()
                except Exception:  # sampling must never kill the job
                    logger.exception("device memory watchdog sample failed")

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="tdl-memory-watchdog")
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._thread is not None:
            self._stop.set()
            self._thread.join(timeout=5)
            self._thread = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


# --------------------------------------------------------------- recompiles

# jax.monitoring listeners are append-only (no unregister), so ONE module
# hook is installed lazily and fans out to whatever watchdogs are active.
_ACTIVE: List["RecompileWatchdog"] = []
_HOOK_LOCK = threading.Lock()
_HOOK_INSTALLED = False
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# -- shared thread announcements (ISSUE 12) ----------------------------------
# The compile-cache monitor (monitoring.compilecache) attributes cache
# hits/misses per fn through the SAME note_signature announcements the
# watchdogs use, but it must work with no RecompileWatchdog installed (a
# production serving replica wants cache counters without churn tracking).
# One module-level store, thread-keyed like the per-watchdog tables.
#
# Event ordering with the persistent cache ON (jax 0.9.0: read in
# jax/_src/compiler.py + interpreters/pxla.py, pinned on CPU by
# tests/test_compile_cache.py, and checked on a TPU v5e by chip_smoke.py's
# warm run — hits > 0 with xla_compiles staying 0): the backend_compile
# duration event wraps jax's WHOLE compile_or_get_cached — it fires on cache
# HITS too (a few ms of deserialization), and the cache hit/miss events fire
# INSIDE the timed block, i.e. BEFORE the duration event. So:
#   - cache_misses → peek the pending announcement (the duration event that
#     follows will claim it for the compile counters);
#   - cache_hits → consume the pending announcement (nothing compiled) and
#     mark the thread, so the duration event that follows is recognized as
#     a RESTORE and skipped — an executable loaded from disk must not count
#     in tdl_xla_compiles_total, or "compiles flat across a restart" would
#     be unmeasurable.
_CC_LOCK = threading.Lock()
_CC_PENDING: Dict[int, Tuple[str, float]] = {}
_CC_HIT_MARK: Dict[int, float] = {}
_ANNOUNCE_EXTRA = False


def enable_announcements() -> None:
    """Make ``note_signature`` record thread announcements (and install the
    compile hook) even with no RecompileWatchdog — the compile-cache
    monitor's attribution path."""
    global _ANNOUNCE_EXTRA
    _ANNOUNCE_EXTRA = True
    _install_hook()


def disable_announcements() -> None:
    """Stop cache-monitor announcements (``common.compile_cache.disable``):
    with no active watchdog either, instrumented call sites go back to
    paying nothing per step."""
    global _ANNOUNCE_EXTRA
    _ANNOUNCE_EXTRA = False


def _cc_note(fn_name: str, signature) -> None:
    # EVERY announcement overwrites (no per-signature memory): a dispatch
    # that hits jax's in-memory jit cache produces no event and the stale
    # announcement is simply replaced by the next one — while a dispatch
    # whose executable cache was dropped (fresh process restoring from
    # disk) is correctly pending when its cache-hit event fires
    with _CC_LOCK:
        _CC_PENDING[threading.get_ident()] = (fn_name, time.monotonic())


def peek_pending_fn() -> Optional[str]:
    """This thread's fresh pending announcement WITHOUT consuming it
    (cache-MISS attribution: the miss event fires before the duration event
    that will claim the announcement for the compile counters)."""
    now = time.monotonic()
    with _CC_LOCK:
        pending = _CC_PENDING.get(threading.get_ident())
    if pending is not None and now - pending[1] <= ATTRIBUTION_WINDOW_S:
        return pending[0]
    return None


def take_pending_fn() -> Optional[str]:
    """Consume this thread's pending announcement (cache-HIT attribution:
    the announced dispatch was satisfied from disk; no compile should claim
    it later). None when nothing fresh is pending."""
    now = time.monotonic()
    with _CC_LOCK:
        pending = _CC_PENDING.pop(threading.get_ident(), None)
    if pending is not None and now - pending[1] <= ATTRIBUTION_WINDOW_S:
        return pending[0]
    return None


def note_cache_hit() -> None:
    """Mark this thread as having just restored an executable from the
    persistent cache: the backend_compile duration event that follows wraps
    the retrieval, not a compile, and will be skipped."""
    with _CC_LOCK:
        _CC_HIT_MARK[threading.get_ident()] = time.monotonic()


def _was_cache_restore(duration: float) -> bool:
    now = time.monotonic()
    with _CC_LOCK:
        mark = _CC_HIT_MARK.pop(threading.get_ident(), None)
    # the hit event fired INSIDE the timed block — it can't be older than
    # the block itself (small slack for listener scheduling)
    return mark is not None and now - mark <= duration + 5.0


def _install_hook() -> None:
    global _HOOK_INSTALLED
    with _HOOK_LOCK:
        if _HOOK_INSTALLED:
            return
        import jax

        def on_duration(event: str, duration: float, **kw) -> None:
            if event == _COMPILE_EVENT:
                tid = threading.get_ident()
                if _was_cache_restore(duration):
                    # deserialized from disk: not a compile — but the
                    # announcement is SPENT, incl. each watchdog's copy, or
                    # the thread's next unannounced compile (within the
                    # 120s window) would inherit the restored fn's label
                    # and mint a phantom per-fn recompile
                    for wd in list(_ACTIVE):
                        with wd._lock:
                            wd._pending.pop(tid, None)
                    return
                # a real compile consumes this thread's announcement (the
                # miss event already peeked it) so a later unannounced
                # compile can't inherit the label
                with _CC_LOCK:
                    _CC_PENDING.pop(tid, None)
                for wd in list(_ACTIVE):
                    wd._on_compile(duration)

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        _HOOK_INSTALLED = True


def active() -> bool:
    """True when an instrumented call site should compute signatures: a
    RecompileWatchdog is installed, or the compile-cache monitor asked for
    announcements (zero-cost when both are off)."""
    return bool(_ACTIVE) or _ANNOUNCE_EXTRA


def note_step() -> None:
    """Advance every active watchdog's step clock (called by the fit
    loops / MetricsListener once per training iteration)."""
    for wd in list(_ACTIVE):
        wd.step()


def note_signature(fn_name: str, signature) -> None:
    """Record a call signature for ``fn_name`` (called by the fit loops
    with the minibatch shape/dtype signature). No-op with no active
    watchdog or cache monitor."""
    if not _ACTIVE and not _ANNOUNCE_EXTRA:
        return
    _cc_note(fn_name, signature)
    for wd in list(_ACTIVE):
        wd.note_signature(fn_name, signature)


def signature_of(*trees) -> Tuple:
    """Hashable (shape, dtype) signature of arbitrary pytrees of arrays —
    what jit keys its executable cache on, minus weak types."""
    import jax

    sig = []
    for leaf in jax.tree.leaves(trees):
        shape = getattr(leaf, "shape", None)
        if shape is None:
            sig.append(repr(leaf))
        else:
            sig.append((tuple(shape), str(getattr(leaf, "dtype", "?"))))
    return tuple(sig)


#: label for compiles no instrumented call site announced (warmup jits of
#: helper functions, evaluation paths, third-party code)
UNATTRIBUTED = "_unattributed"

#: pending signature→compile attributions older than this are stale (the
#: noted call hit jax's executable cache and never compiled)
ATTRIBUTION_WINDOW_S = 120.0


class RecompileWatchdog:
    """Counts XLA compiles / compile seconds — attributed per jitted
    function — and warns on shape-churn.

    Three correlated signals (ISSUE 10 layer 2):

    - every backend compile (via ``jax.monitoring``) increments
      ``tdl_xla_compiles_total{fn}`` / ``tdl_xla_compile_seconds_total{fn}``.
      Attribution: an instrumented fit loop calls :func:`note_signature`
      immediately before dispatch; a NEW signature becomes that THREAD's
      pending announcement, and the next backend-compile event on the same
      thread claims it (compiles run synchronously on the dispatching
      thread; an announcement whose call hit jax's executable cache is
      overwritten by the thread's next one, never misattributed). Compiles
      with no pending announcement land under ``fn="_unattributed"``. Each
      also leaves a ``compile`` event (fn, signature, seconds) in the flight
      recorder, so churn offenders appear in ``postmortem.json``;
    - when the same function accumulates ≥ ``churn_threshold`` distinct
      signatures within ``window_steps`` steps, a warning is logged and
      ``tdl_shape_churn_warnings_total`` increments;
    - the per-fn signature table is an LRU bounded at
      ``max_signatures_per_fn`` (true shape churn would otherwise grow it
      without bound on long runs); evictions are exported as
      ``tdl_jit_signature_evictions_total{fn}`` instead of leaking memory.

    Use as a context manager (or ``install()``/``close()``); inactive
    instances cost nothing on the hot path.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 window_steps: int = 50, churn_threshold: int = 3,
                 max_signatures_per_fn: int = 512):
        self.registry = registry or get_registry()
        self.window_steps = max(1, window_steps)
        self.churn_threshold = max(2, churn_threshold)
        self.max_signatures_per_fn = max(1, max_signatures_per_fn)
        r = self.registry
        self._compiles = r.counter(
            "tdl_xla_compiles_total",
            "XLA backend compiles observed, attributed to the jitted "
            "function whose new arg-shape signature triggered them",
            labels=("fn",))
        self._compile_seconds = r.counter(
            "tdl_xla_compile_seconds_total",
            "Seconds spent in XLA backend compiles, per attributed function",
            labels=("fn",))
        self._churn = r.counter(
            "tdl_shape_churn_warnings_total",
            "Shape-churn warnings (same function compiled repeatedly)")
        self._sig_counter = r.counter(
            "tdl_jit_new_signatures_total",
            "Distinct jit call signatures first seen, per function",
            labels=("fn",))
        self._evictions = r.counter(
            "tdl_jit_signature_evictions_total",
            "Signatures evicted from the bounded per-fn LRU table (churn so "
            "sustained the watchdog stopped remembering old shapes)",
            labels=("fn",))
        self._lock = threading.Lock()
        self._step = 0
        self._seen: Dict[str, OrderedDict] = defaultdict(OrderedDict)  # LRU
        self._recent: Dict[str, deque] = defaultdict(deque)  # (step,) of new sigs
        self._warned_at: Dict[str, int] = {}
        # per-THREAD latest unclaimed (fn, signature, noted_at): a compile
        # runs synchronously on the thread that dispatched it, so claiming is
        # thread-keyed — a stale announcement (new-to-us signature that hit
        # jax's own executable cache, e.g. after an LRU eviction) is simply
        # overwritten by that thread's next announcement instead of shifting
        # a shared FIFO and misattributing every later compile
        self._pending: Dict[int, Tuple[str, object, float]] = {}
        self.compile_count = 0
        self.compile_seconds = 0.0
        self.per_fn_compiles: Dict[str, int] = defaultdict(int)
        self.per_fn_compile_seconds: Dict[str, float] = defaultdict(float)

    # -- lifecycle ---------------------------------------------------------

    def install(self) -> "RecompileWatchdog":
        _install_hook()
        if self not in _ACTIVE:
            _ACTIVE.append(self)
        return self

    def close(self) -> None:
        if self in _ACTIVE:
            _ACTIVE.remove(self)

    def __enter__(self):
        return self.install()

    def __exit__(self, *exc):
        self.close()
        return False

    # -- signals -----------------------------------------------------------

    def _on_compile(self, duration: float) -> None:
        now = time.monotonic()
        with self._lock:
            self.compile_count += 1
            self.compile_seconds += duration
            fn, sig = UNATTRIBUTED, None
            claimed = self._pending.pop(threading.get_ident(), None)
            # staleness is judged at compile START (the event fires at the
            # END and carries the duration): a bert-large compile can run
            # longer than the window and must still be attributed
            if (claimed is not None
                    and now - duration - claimed[2] <= ATTRIBUTION_WINDOW_S):
                fn, sig = claimed[0], claimed[1]
            self.per_fn_compiles[fn] += 1
            self.per_fn_compile_seconds[fn] += duration
        self._compiles.labels(fn).inc()
        self._compile_seconds.labels(fn).inc(duration)
        # black-box breadcrumb: postmortems list churn offenders from these
        flight.record("compile", fn=fn, seconds=round(duration, 4),
                      signature=None if sig is None else repr(sig))

    def step(self) -> None:
        with self._lock:
            self._step += 1

    def note_signature(self, fn_name: str, signature) -> None:
        evicted = 0
        with self._lock:
            seen = self._seen[fn_name]
            if signature in seen:
                seen.move_to_end(signature)  # LRU touch
                return
            seen[signature] = None
            while len(seen) > self.max_signatures_per_fn:
                seen.popitem(last=False)
                evicted += 1
            self._pending[threading.get_ident()] = (
                fn_name, signature, time.monotonic())
            step = self._step
            recent = self._recent[fn_name]
            recent.append(step)
            while recent and recent[0] < step - self.window_steps:
                recent.popleft()
            fresh = len(recent)
            warned = self._warned_at.get(fn_name)
            should_warn = (fresh >= self.churn_threshold and
                           (warned is None or step - warned >= self.window_steps))
            if should_warn:
                self._warned_at[fn_name] = step
        self._sig_counter.labels(fn_name).inc()
        if evicted:
            self._evictions.labels(fn_name).inc(evicted)
        if should_warn:
            self._churn.inc()
            logger.warning(
                "recompile watchdog: %s saw %d distinct input signatures in "
                "the last %d steps — shape churn recompiles the XLA "
                "executable each time; pad or bucket your minibatch shapes",
                fn_name, fresh, self.window_steps)

    # -- reading -----------------------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            return {
                "compiles": self.compile_count,
                "compile_seconds": self.compile_seconds,
                "steps": self._step,
                "signatures": {k: len(v) for k, v in self._seen.items()},
                "per_fn_compiles": dict(self.per_fn_compiles),
                "per_fn_compile_seconds": {
                    k: round(v, 4)
                    for k, v in self.per_fn_compile_seconds.items()},
            }
