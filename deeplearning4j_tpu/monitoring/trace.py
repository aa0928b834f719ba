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

A thread may hand its spans' durations to ONE :class:`StepPhaseRecorder`
(:func:`feed_spans_to`): the serving loop does, for its life, and so has an
account of its steps with no profiler listening. A thread that did not pays
one attribute lookup a span.
"""

from __future__ import annotations

import threading
import time
from typing import Optional


class _ThreadState(threading.local):
    # class-level defaults: a thread that set neither reads them at the cost
    # of a plain attribute lookup (a missing attribute of a bare
    # ``threading.local`` costs an exception inside ``getattr``)
    stack = None     # qualified names of this thread's open spans, innermost last
    recorder = None  # the StepPhaseRecorder this thread's spans feed


_tls = _ThreadState()

_trace_profiler = None  # OpProfiler every span also records into (optional)


def set_trace_profiler(profiler) -> None:
    """Attach an ``OpProfiler`` that every span records into (give it
    ``ProfilerConfig(trace_events=True)`` to capture the events). Pass
    ``None`` to detach."""
    global _trace_profiler
    _trace_profiler = profiler


def get_trace_profiler():
    return _trace_profiler


def current_span_path() -> str:
    """Qualified name of the innermost active span ('' outside any span)."""
    stack = _tls.stack
    return stack[-1] if stack else ""


def feed_spans_to(recorder: Optional["StepPhaseRecorder"]) -> None:
    """From now on every span that closes on THIS thread adds its exclusive
    time to ``recorder`` (:meth:`StepPhaseRecorder.closed`); ``None`` ends
    it. The thread owns the recorder: nothing else writes to it."""
    _tls.recorder = recorder


def profiler_listening() -> bool:
    """Whether a profiler session is capturing annotations right now (the
    flag every ``TraceAnnotation`` checks; about 20 ns)."""
    return (_annotation_types or _annotations())[0].is_enabled()


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
    "sched.gauges",
    # the slot pool, on the loop thread (models/paged_decode.py)
    "kv.prefill", "kv.prefill.fetch",
    "kv.step.prepare", "kv.step.upload", "kv.step.dispatch", "kv.step.fetch",
    "kv.step.land",
)

#: the names of :data:`SERVING_SPANS` that close on the executor's loop
#: thread: the phase columns of its step account
LOOP_SPANS = tuple(n for n in SERVING_SPANS if not n.startswith("door."))

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
        # a hot path: ``qualified_name``, ``start_ns`` and the annotation are
        # set by ``__enter__`` (reading them before it is an AttributeError)
        self.name = name
        self.stats = stats or {}
        self._profiler = profiler
        self._histogram = histogram
        self._step_num = step_num
        self.duration_s: Optional[float] = None

    def __enter__(self):
        plain, stepped = _annotation_types or _annotations()
        stack = _tls.stack
        if stack is None:
            stack = _tls.stack = []
        self.qualified_name = (stack[-1] + "/" + self.name if stack
                               else self.name)
        stack.append(self.qualified_name)
        # StepTraceAnnotation marks step boundaries for XProf's step-time
        # analysis; TraceAnnotation is a plain named region. Outside a
        # profiler session a span makes neither: it asks the flag an
        # annotation would check (a span open when a session starts is never
        # written either way)
        if not plain.is_enabled():
            self._annotation = None
        else:
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
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        stack = _tls.stack
        stack.pop()
        self.duration_s = dur_ns / 1e9
        prof = self._profiler if self._profiler is not None else _trace_profiler
        if prof is not None:
            prof.record(self.qualified_name, dur_ns)
        if self._histogram is not None:
            self._histogram.observe(self.duration_s)
        recorder = _tls.recorder
        if recorder is not None:
            recorder.closed(self.name, dur_ns, len(stack), self.stats)
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


# -- step-time attribution (ISSUE 7 tentpole, layer 3; ISSUE 39) -------------
#
# The signals were already captured but scattered: input wait in
# DevicePrefetchIterator, h2d seconds worker-side, compute implicit in the
# step histogram, collective bytes (not seconds) in the trainer. The
# StepPhaseRecorder unifies them into ONE per-step breakdown: phases recorded
# as (nesting-aware, exclusive-time) spans, exported simultaneously as
# chrome-trace events (via the module trace profiler, when attached), as the
# `tdl_step_phase_seconds{phase=...}` histogram family, and as the
# phase-percentage table in bench.py's telemetry block. The serving loop uses
# the same class with a ring: its phases are the spans that close on its
# thread, a row a step, no histogram.

#: canonical phase names; recorders accept others but the bench table and
#: OBSERVABILITY.md catalog enumerate these four
STEP_PHASES = ("input", "h2d", "compute", "collective")

#: what a row's ``segment`` column says about the profiler: no session seen
#: yet in this process, one listening, none listening after one was
SEGMENTS = ("untraced", "traced", "untraced_after_trace")

_MAX_DEPTH = 16  # phases nested deeper than this are counted into their parent


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

    __slots__ = ("_rec", "_name", "_span", "_t0")

    def __init__(self, rec: "StepPhaseRecorder", name: str):
        self._rec = rec
        self._name = name
        self._span = None

    def __enter__(self):
        if _trace_profiler is not None:
            self._span = Span(self._name)
            self._span.__enter__()
        self._rec._depth += 1
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur_ns = time.perf_counter_ns() - self._t0
        rec = self._rec
        rec._depth -= 1
        rec.closed(self._name, dur_ns, rec._depth)
        if self._span is not None:
            self._span.__exit__(*exc)
        return False


class StepPhaseRecorder:
    """THE sum of a loop's phases, a step at a time, for the one thread that
    runs the loop. A phase is timed by :meth:`phase` (a fit loop) or is a
    span that closed on a thread which feeds this recorder
    (:func:`feed_spans_to`: the serving loop); either way it counts its
    EXCLUSIVE nanoseconds, a phase nested in another only toward itself.
    :meth:`step_done` closes a step, :meth:`discard` says that what was
    accumulated belongs to no step (it is kept apart, by name).

    Without a ring (a fit loop) a step's phases are observed into the
    ``tdl_step_phase_seconds`` family and summed for :meth:`summary`'s
    percentage table. With ``ring=N`` (the serving loop) nothing is observed
    into a histogram: a step is one row of a fixed ``int64`` table that keeps
    the newest N steps — ``fields`` (the caller's own numbers), ``segment``,
    ``period``, the exclusive nanoseconds of every phase named in
    ``columns``, and ``other`` = period less their sum, so a row's phases and
    ``other`` add up to its period exactly. :meth:`snapshot` copies the
    table for a reader on another thread, who takes the quantiles."""

    def __init__(self, registry=None, *, ring: int = 0, columns=(), fields=()):
        self._acc: dict = {}      # phase -> exclusive ns of the open step
        self._children = [0] * (_MAX_DEPTH + 1)  # by depth: ns of closed phases
        self._depth = 0           # of the open phase() timers
        self._steps = 0
        self._segment = 0
        self._segment_steps = [0] * len(SEGMENTS)
        #: by segment: phase -> exclusive ns that belonged to no step
        self._outside = [{} for _ in SEGMENTS]
        #: stats of the last span of each name that closed on the feeding thread
        self.last_stats: dict = {}
        self._columns = tuple(columns)
        self._fields = tuple(fields)
        if ring:
            import struct

            import numpy as np

            width = len(self._fields) + 2 + len(self._columns) + 1
            self._ring = np.zeros((ring, width), np.int64)
            # a row is written as packed bytes: half the time of handing
            # numpy a tuple
            self._row_bytes = 8 * width
            self._write = struct.Struct(f"={width}q").pack_into
            self._ring_bytes = memoryview(self._ring).cast("B")
        else:
            self._hist = step_phase_histogram(registry)
            self._ring = None
            self._totals: dict = {}
            self._wall_ns = 0
            self._last_done: Optional[int] = None

    def phase(self, name: str) -> _PhaseTimer:
        """``with recorder.phase("input"): ds = next(it)``"""
        return _PhaseTimer(self, name)

    def closed(self, name: str, dur_ns: int, depth: int, stats=None) -> None:
        """A phase ``depth`` phases deep took ``dur_ns`` and has closed: what
        the phases nested in it took is theirs, the rest its own."""
        if depth >= _MAX_DEPTH:
            return  # its parent keeps the time
        children = self._children
        inner = children[depth + 1]
        if inner:
            children[depth + 1] = 0
        if depth:
            children[depth] += dur_ns
        acc = self._acc
        acc[name] = acc.get(name, 0) + dur_ns - inner
        if stats:
            self.last_stats[name] = stats

    def add(self, name: str, seconds: float) -> None:
        """Attribute already-measured seconds (e.g. an h2d counter delta)."""
        self._acc[name] = self._acc.get(name, 0) + int(seconds * 1e9)

    def pending_ns(self, name: str) -> Optional[int]:
        """Exclusive ns of phase ``name`` in the open step (None: it has not
        closed since the last step)."""
        return self._acc.get(name)

    def discard(self) -> None:
        """What accumulated since the last :meth:`step_done` belongs to no
        step. For loop boundaries: the ``next()`` that raises StopIteration
        still records an "input" slice — without the discard it would
        pollute the NEXT epoch's (or fit call's) first step; the serving
        loop's idle waits and admissions lie between two runs of steps.
        Nothing is observed; the time is kept by name (:meth:`snapshot`)."""
        if self._acc:
            outside = self._outside[self._segment]
            for name, ns in self._acc.items():
                outside[name] = outside.get(name, 0) + ns
            self._acc = {}

    def profiler_seen(self, listening: bool) -> None:
        """Tell the recorder whether a profiler session is listening: steps
        and discarded time fall into :data:`SEGMENTS` by it from now on."""
        if listening:
            self._segment = 1
        elif self._segment == 1:
            self._segment = 2

    def step_done(self, period_ns: Optional[int] = None, fields=()) -> None:
        """Close a step. With a ring: write its row (``period_ns`` is the
        step's whole time on the caller's clock, ``fields`` the caller's
        numbers in the order the recorder was given their names)."""
        acc, self._acc = self._acc, {}
        self._steps += 1
        self._segment_steps[self._segment] += 1
        if self._ring is not None:
            phases = [acc.get(name, 0) for name in self._columns]
            self._write(
                self._ring_bytes,
                (self._steps - 1) % len(self._ring) * self._row_bytes,
                *fields, self._segment, period_ns, *phases,
                period_ns - sum(phases))
            return
        for name, ns in acc.items():
            self._hist.labels(name).observe(ns / 1e9)
            self._totals[name] = self._totals.get(name, 0) + ns
        now = time.perf_counter_ns()
        if self._last_done is not None:
            self._wall_ns += now - self._last_done
        else:
            # first step has no prior boundary: its wall is what we measured
            self._wall_ns += sum(acc.values())
        self._last_done = now

    def snapshot(self) -> dict:
        """For a reader on another thread: ``columns`` (the row's names:
        the fields, ``segment``, ``period``, the phases, ``other``),
        ``rows`` (a copy of the newest steps' rows, ns; in no order),
        ``steps`` (by segment, ALL steps: the ring forgets rows, not
        counts) and ``outside_s`` (by segment: phase -> seconds that
        belonged to no step)."""
        out = {"steps": list(self._segment_steps),
               # dict(o): the loop thread may add a name meanwhile
               "outside_s": [{k: v / 1e9 for k, v in dict(o).items()}
                             for o in self._outside]}
        if self._ring is not None:
            out["columns"] = (*self._fields, "segment", "period",
                              *self._columns, "other")
            out["rows"] = self._ring[:min(self._steps, len(self._ring))].copy()
        return out

    def summary(self) -> dict:
        """Phase-percentage table over the recorded steps' total wall (a
        recorder without a ring). The canonical phases always appear (0.0
        when never recorded) so the input/h2d/compute/collective breakdown
        reads complete; `other_pct` is the unattributed remainder — near
        zero when the loop is fully instrumented, which is what "sums to
        ~100%" means."""
        totals = {k: v / 1e9 for k, v in self._totals.items()}
        wall = max(self._wall_ns / 1e9, sum(totals.values()), 1e-9)
        phases = {}
        for name in list(STEP_PHASES) + sorted(set(totals) - set(STEP_PHASES)):
            s = totals.get(name, 0.0)
            phases[name] = {"seconds": round(s, 4),
                            "pct": round(100.0 * s / wall, 2)}
        attributed = sum(p["pct"] for p in phases.values())
        return {"steps": self._steps, "wall_seconds": round(wall, 4),
                "phases": phases,
                "other_pct": round(max(0.0, 100.0 - attributed), 2)}
