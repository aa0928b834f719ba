"""Nestable host-side spans aligned with the XProf device timeline.

Reference: SameDiff's ``ProfilingListener`` emits host-side chrome-trace
events; XProf/XPlane owns the device timeline (SURVEY §5.1). The two views
were previously uncorrelated. A :func:`span` does three things at once:

- wraps ``jax.profiler.TraceAnnotation`` (or ``StepTraceAnnotation`` when a
  ``step_num`` is given) so the span shows up on the device trace whenever an
  XProf capture is active — host spans and HLO timelines line up by name, on
  the profiler's own clock, and the span's keywords (``request_id``,
  ``step``, ``live``, ``bucket``) ride the trace event as its stats;
- records a chrome-trace complete event into an :class:`~..ops.profiler.
  OpProfiler` (the one attached via :func:`set_trace_profiler`, or an
  explicit ``profiler=``), so ONE ``to_chrome_trace`` file carries both op
  events and span events;
- optionally observes the span duration into a registry histogram.

Spans nest: names are qualified with the enclosing span path
(``fit/step/h2d``), per thread. It is the ONE way to open a span on a hot
path; the names used on the served path are declared in
:data:`SERVING_SPANS`.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

_tls = threading.local()

_trace_profiler = None  # OpProfiler every span also records into (optional)


def set_trace_profiler(profiler) -> None:
    """Attach an ``OpProfiler`` that every span records into (give it
    ``ProfilerConfig(trace_events=True)`` to capture the events). Pass
    ``None`` to detach."""
    global _trace_profiler
    _trace_profiler = profiler


def get_trace_profiler():
    return _trace_profiler


def _stack():
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def current_span_path() -> str:
    """Qualified name of the innermost active span ('' outside any span)."""
    return "/".join(_stack())


#: THE span vocabulary of the served path (door -> sched -> kv), as
#: ``flight.EVENT_KINDS`` is for flight events. Every ``span("...")`` literal
#: under ``serving/`` and ``models/paged_decode.py`` must be declared here
#: (tests/test_serving_spans.py AST lint) and tabled in
#: docs/OBSERVABILITY.md ("Served-path spans"): a reader of a device trace
#: finds the host's side of a gap by these names.
SERVING_SPANS = (
    # one handler thread per request (serving/json_server.py)
    "door.request", "door.read", "door.parse", "door.wait",
    "door.serialize", "door.write",
    # the executor's loop thread (serving/executor.py)
    "sched.idle", "sched.admit", "sched.decode_step", "sched.retire",
    # the slot pool, on the loop thread (models/paged_decode.py)
    "kv.prefill", "kv.prefill.fetch",
    "kv.step.upload", "kv.step.dispatch", "kv.step.fetch",
)

_annotation_types = None  # (TraceAnnotation, StepTraceAnnotation), on first use


def _annotations():
    # jax stays out of this module's import (supervisors and stub replicas
    # import monitoring and must not pay for jax) and out of __enter__
    global _annotation_types
    if _annotation_types is None:
        from jax.profiler import StepTraceAnnotation, TraceAnnotation

        _annotation_types = (TraceAnnotation, StepTraceAnnotation)
    return _annotation_types


class Span:
    """One host span: ``name``, ``start_ns`` / ``duration_s`` (perf_counter),
    its parent (``qualified_name``, nesting per thread) and ``stats``, the
    request or step it belongs to. The profiler's trace event carries the
    stats too, so a device trace is joined to a request by number."""

    __slots__ = ("name", "stats", "_profiler", "_histogram", "_step_num",
                 "_annotation", "qualified_name", "start_ns", "duration_s")

    def __init__(self, name: str, profiler=None, histogram=None,
                 step_num: Optional[int] = None, stats: Optional[dict] = None):
        self.name = name
        self.stats = stats or {}
        self._profiler = profiler
        self._histogram = histogram
        self._step_num = step_num
        self._annotation = None
        self.qualified_name: Optional[str] = None
        self.start_ns: Optional[int] = None
        self.duration_s: Optional[float] = None

    def __enter__(self):
        plain, stepped = _annotations()
        stack = _stack()
        stack.append(self.name)
        self.qualified_name = "/".join(stack)
        # StepTraceAnnotation marks step boundaries for XProf's step-time
        # analysis; TraceAnnotation is a plain named region. Outside a
        # profiler session either costs one flag check
        if self._step_num is not None:
            self._annotation = stepped(self.name, step_num=self._step_num,
                                       **self.stats)
        else:
            self._annotation = plain(self.name, **self.stats)
        self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur_ns = time.perf_counter_ns() - self.start_ns
        self._annotation.__exit__(*exc)
        _stack().pop()
        self.duration_s = dur_ns / 1e9
        prof = self._profiler if self._profiler is not None else _trace_profiler
        if prof is not None:
            prof.record(self.qualified_name, dur_ns)
        if self._histogram is not None:
            self._histogram.observe(self.duration_s)
        return False


def span(name: str, profiler=None, histogram=None, **stats) -> Span:
    """Open a nestable host span: ``with span("h2d"): ...``. Keywords become
    the trace event's stats: ``span("sched.decode_step", step=7, live=3)``."""
    return Span(name, profiler=profiler, histogram=histogram, stats=stats)


def step_span(step_num: int, name: str = "train",
              profiler=None, histogram=None) -> Span:
    """A span marking ONE training step (XProf StepTraceAnnotation), so the
    device trace's step-time view and the host cadence agree on boundaries."""
    return Span(name, profiler=profiler, histogram=histogram,
                step_num=step_num)


# -- step-time attribution (ISSUE 7 tentpole, layer 3) -----------------------
#
# The signals were already captured but scattered: input wait in
# DevicePrefetchIterator, h2d seconds worker-side, compute implicit in the
# step histogram, collective bytes (not seconds) in the trainer. The
# StepPhaseRecorder unifies them into ONE per-step breakdown: phases recorded
# as (nesting-aware, exclusive-time) spans, exported simultaneously as
# chrome-trace events (via the module trace profiler, when attached), as the
# `tdl_step_phase_seconds{phase=...}` histogram family, and as the
# phase-percentage table in bench.py's telemetry block.

#: canonical phase names; recorders accept others but the bench table and
#: OBSERVABILITY.md catalog enumerate these four
STEP_PHASES = ("input", "h2d", "compute", "collective")


def step_phase_histogram(registry=None):
    """Get-or-create the `tdl_step_phase_seconds` family — one declaration
    site so trainers, masters, bench.py and tests agree on name + labels."""
    if registry is None:
        from .registry import get_registry

        registry = get_registry()
    return registry.histogram(
        "tdl_step_phase_seconds",
        "Seconds of one train step attributed to a phase (exclusive time: "
        "a phase nested inside another counts only toward itself)",
        labels=("phase",))


class _PhaseTimer:
    """Context manager timing one phase occurrence. Host timing only unless
    a trace profiler is attached — then a full :class:`Span` rides along so
    the phase also lands on the chrome-trace/XProf timelines."""

    __slots__ = ("_rec", "_name", "_span", "_t0", "_children")

    def __init__(self, rec: "StepPhaseRecorder", name: str):
        self._rec = rec
        self._name = name
        self._span = None

    def __enter__(self):
        if _trace_profiler is not None:
            self._span = Span(self._name)
            self._span.__enter__()
        self._t0 = time.perf_counter()
        self._children = 0.0
        self._rec._frames.append(self)
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._t0
        frames = self._rec._frames
        frames.pop()
        # exclusive time: my nested phases already claimed their share
        self._rec.add(self._name, max(0.0, dur - self._children))
        if frames:
            frames[-1]._children += dur
        if self._span is not None:
            self._span.__exit__(*exc)
        return False


class StepPhaseRecorder:
    """Accumulates per-phase seconds across one step, observes them into the
    histogram family at :meth:`step_done`, and keeps running totals for the
    bench phase-percentage table. One instance per fit loop thread."""

    def __init__(self, registry=None):
        self._hist = step_phase_histogram(registry)
        self._acc: dict = {}
        self._totals: dict = {}
        self._frames: list = []
        self._steps = 0
        self._wall = 0.0
        self._last_done: Optional[float] = None

    def phase(self, name: str) -> _PhaseTimer:
        """``with recorder.phase("input"): ds = next(it)``"""
        return _PhaseTimer(self, name)

    def add(self, name: str, seconds: float) -> None:
        """Attribute already-measured seconds (e.g. an h2d counter delta)."""
        self._acc[name] = self._acc.get(name, 0.0) + float(seconds)

    def discard(self) -> None:
        """Drop phase time accumulated since the last :meth:`step_done`.
        For loop boundaries: the ``next()`` that raises StopIteration still
        records an "input" slice, which belongs to no step — without the
        discard it would pollute the NEXT epoch's (or fit call's) first
        step."""
        self._acc = {}

    def step_done(self) -> None:
        for name, s in self._acc.items():
            self._hist.labels(name).observe(s)
            self._totals[name] = self._totals.get(name, 0.0) + s
        now = time.perf_counter()
        if self._last_done is not None:
            self._wall += now - self._last_done
        else:
            # first step has no prior boundary: its wall is what we measured
            self._wall += sum(self._acc.values())
        self._last_done = now
        self._steps += 1
        self._acc = {}

    def summary(self) -> dict:
        """Phase-percentage table over the recorded steps' total wall.
        The canonical phases always appear (0.0 when never recorded) so the
        input/h2d/compute/collective breakdown reads complete; `other_pct`
        is the unattributed remainder — near zero when the loop is fully
        instrumented, which is what "sums to ~100%" means."""
        wall = max(self._wall, sum(self._totals.values()), 1e-9)
        phases = {}
        for name in list(STEP_PHASES) + sorted(set(self._totals) - set(STEP_PHASES)):
            s = self._totals.get(name, 0.0)
            phases[name] = {"seconds": round(s, 4),
                            "pct": round(100.0 * s / wall, 2)}
        attributed = sum(p["pct"] for p in phases.values())
        return {"steps": self._steps, "wall_seconds": round(wall, 4),
                "phases": phases,
                "other_pct": round(max(0.0, 100.0 - attributed), 2)}
