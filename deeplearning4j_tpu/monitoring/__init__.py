"""Unified telemetry subsystem (SURVEY §2.4 C14 / §5.1 observability tier).

- :mod:`.registry` — labeled counters / gauges / fixed-bucket histograms with
  Prometheus text exposition (served by ``UIServer`` at ``/metrics``) and a
  JSON snapshot (``/metrics.json``, ``bench.py`` telemetry block);
- :mod:`.trace` — nestable host spans aligned with XProf device traces,
  feeding ``OpProfiler`` chrome-trace files;
- :mod:`.watchdogs` — device-memory watermark sampler + XLA recompile /
  shape-churn detector;
- :mod:`.listener` — ``MetricsListener``, the TrainingListener bridge that
  wires a network's fit loop into the registry;
- :mod:`.aggregate` — per-process metrics spools merged into ONE
  proc/rank-labeled ``/metrics`` with derived straggler gauges (ISSUE 7);
- :mod:`.flight` — the flight recorder: a bounded ring of structured events
  every process appends to, merged into ``postmortem.json`` on gang failure;
- :mod:`.costmodel` — per-layer FLOPs/bytes attribution joined against XLA
  ``cost_analysis()`` of the compiled step, plus the live-HBM breakdown
  (ISSUE 10);
- :mod:`.alerts` — declarative SLO rules evaluated at scrape time, served
  at ``UIServer /alerts``, firing/clearing edges recorded into the flight
  ring (windowed rules, rates and percentiles read the history ring);
- :mod:`.history` — the time dimension: a bounded ring of timestamped
  registry snapshots, per-proc spools merged at read time, served at
  ``UIServer /history``, plus the shared window math (rates, deltas,
  bucket-interpolated quantiles) every windowed consumer uses (ISSUE 11);
- :mod:`.slo` — declarative SLO objectives compiled against the history
  ring: attainment, error-budget remaining and burn rate exported as
  ``tdl_slo_*`` gauges and served at ``UIServer /slo``;
- :mod:`.compilecache` — persistent-compile-cache hit/miss counters,
  attributed per fn through the watchdogs' thread announcements (ISSUE 12;
  installed by ``common.compile_cache.enable``).
"""

from .aggregate import MetricsSpooler, maybe_spool, merged_prometheus
from .alerts import AlertEngine, AlertRule, default_rules
from .history import HistoryRing, HistoryView
from .slo import SloObjective, SloTracker, default_objectives
from .costmodel import (cost_table, layer_costs, live_hbm_breakdown,
                        net_hbm_breakdown, xla_step_cost)
from .etl import etl_metrics
from .flight import FlightRecorder, get_flight_recorder, set_flight_recorder
from .heartbeat import HeartbeatWriter, maybe_beat, read_heartbeat
from .listener import MetricsListener
from .partition import partition_metrics
from .registry import (Counter, Gauge, Histogram, MetricsRegistry,
                       get_registry)
from .serving import serving_metrics
from .trace import (SERVING_SPANS, Span, StepPhaseRecorder, current_span_path,
                    set_trace_profiler, span, step_phase_histogram, step_span)
from .watchdogs import (DeviceMemoryWatchdog, RecompileWatchdog, active,
                        host_rss_bytes, note_signature, note_step,
                        signature_of)

__all__ = [
    "AlertEngine",
    "AlertRule",
    "default_rules",
    "HistoryRing",
    "HistoryView",
    "SloObjective",
    "SloTracker",
    "default_objectives",
    "cost_table",
    "layer_costs",
    "live_hbm_breakdown",
    "net_hbm_breakdown",
    "xla_step_cost",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "get_registry",
    "etl_metrics",
    "partition_metrics",
    "serving_metrics",
    "MetricsListener",
    "MetricsSpooler",
    "maybe_spool",
    "merged_prometheus",
    "FlightRecorder",
    "get_flight_recorder",
    "set_flight_recorder",
    "HeartbeatWriter",
    "maybe_beat",
    "read_heartbeat",
    "SERVING_SPANS",
    "Span",
    "StepPhaseRecorder",
    "step_phase_histogram",
    "span",
    "step_span",
    "current_span_path",
    "set_trace_profiler",
    "DeviceMemoryWatchdog",
    "RecompileWatchdog",
    "host_rss_bytes",
    "note_signature",
    "note_step",
    "signature_of",
]
