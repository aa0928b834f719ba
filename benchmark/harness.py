"""What every runner shares: the set-up clock, the compile counters, the
context a runner receives, device memory, and the traced window."""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import shutil
import time
from typing import Any, Callable, List, Optional


class SetupClock:
    """Set-up seconds by phase, from process start to the window."""

    def __init__(self, t_process: float, devices=()):
        self.t_process = t_process
        self.devices = devices
        self._last = time.perf_counter()
        self._split = {}
        self._peak = {}  # device peak bytes after each phase: whose peak is it?

    def mark(self, phase: str, since: Optional[float] = None) -> None:
        now = time.perf_counter()
        start = self._last if since is None else since
        self._split[phase] = self._split.get(phase, 0.0) + now - start
        if self.devices:
            self._peak[phase] = memory_peak_bytes(self.devices)
        self._last = time.perf_counter()

    def setup_s(self) -> float:
        """Call at the instant the window opens."""
        return time.perf_counter() - self.t_process

    def split(self) -> dict:
        return {k: round(v, 3) for k, v in self._split.items()}

    def peak_bytes_after(self) -> dict:
        return dict(self._peak)


class CompileCounters:
    """The program's own compile-cache and XLA-compile counters
    (``monitoring/compilecache``, ``RecompileWatchdog``)."""

    def __init__(self, cache_dir, watchdog):
        self.cache_dir = cache_dir
        self._watchdog = watchdog
        self._window_start = None
        self.compiles_in_window = None

    @classmethod
    def install(cls) -> "CompileCounters":
        from deeplearning4j_tpu.common import compile_cache
        from deeplearning4j_tpu.monitoring import RecompileWatchdog

        # the repo's one rule: JAX_COMPILATION_CACHE_DIR, else
        # <checkout>/.jax_cache; the benchmark never names a path
        return cls(compile_cache.enable(), RecompileWatchdog().install())

    def xla_compiles(self) -> int:
        return int(self._watchdog.stats()["compiles"])

    def cache(self) -> dict:
        from deeplearning4j_tpu.monitoring import compilecache

        s = compilecache.stats()
        return {"hits": round(sum(s["hits"].values())),
                "misses": round(sum(s["misses"].values()))}

    def _programs_made(self) -> int:
        # compiled, or loaded from the cache (a hit is not counted as a
        # compile under jax 0.9.0): either way a program was made
        return self.xla_compiles() + self.cache()["hits"]

    def open_window(self) -> None:
        self._window_start = self._programs_made()

    def close_window(self) -> None:
        self.compiles_in_window = self._programs_made() - self._window_start

    def summary(self) -> dict:
        return {"cache_hits": self.cache()["hits"],
                "cache_misses": self.cache()["misses"],
                "xla_compiles": self.xla_compiles(),
                "compiles_in_window": self.compiles_in_window}


@dataclasses.dataclass
class Context:
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    on_tpu: bool
    devices: List[Any]
    peaks: Optional[dict]
    clock: SetupClock
    counters: CompileCounters
    emit: Callable[[dict], None]
    root: str
    sweep: Optional[List[float]] = None
    dump_events: Optional[str] = None


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def memory_limit_bytes(devices) -> Optional[int]:
    limits = [(d.memory_stats() or {}).get("bytes_limit") for d in devices]
    return int(min(limits)) if all(limits) else None


def annotate(name: str):
    """A host span in the profiler's own trace (and a no-op outside one)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class TracedWindow:
    """A few seconds of profiler trace inside the measured window, reduced
    to plain events and then to busy time, per-op durations and gaps."""

    def __init__(self, ctx: Context):
        self.ctx = ctx
        # inside the checkout, at a fixed path; .gitignore lists it
        self.dir = os.path.join(ctx.root, ".bench_trace")

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans come from annotations only
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def reduce(self) -> Optional[dict]:
        from benchmark import tracereduce

        files = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not files:
            return None
        events = tracereduce.load_events(files[0])
        if self.ctx.dump_events:
            os.makedirs(os.path.dirname(os.path.abspath(self.ctx.dump_events)),
                        exist_ok=True)
            with open(self.ctx.dump_events, "w") as f:
                json.dump(events, f)
            with open(self.ctx.dump_events + ".describe.txt", "w") as f:
                f.write(tracereduce.describe(files[0]))
        shutil.rmtree(self.dir, ignore_errors=True)
        return tracereduce.reduce(events, n_devices=len(self.ctx.devices))
