"""Observability: histograms, a metrics registry, and a span tracer.

The runtime's headline claims (throughput, per-node idle time) are
observability claims, yet the reference measures them with a stopwatch in
its test harness (reference test/test.py:25-37) and our own
``PipelineMetrics`` only held averages.  This package gives the runtime a
first-class, always-on-cheap telemetry layer:

* :class:`LatencyHistogram` — log-bucketed, mergeable, p50/p95/p99/max.
* :class:`MetricsRegistry` — process-wide named counters / gauges /
  histograms with a JSON snapshot and Prometheus-style text exposition.
* :class:`Tracer` — trace_id/span_id spans with parent links and
  monotonic timestamps, exportable as Chrome trace-event JSON (open the
  file at https://ui.perfetto.dev).
* :func:`span` — the one primitive the hot paths name their phases
  with: histogram, tracer span and profiler annotation from one pair of
  clock reads (names from ``obs/profile.py``'s phase tables).

Cost contract: counters are plain int attributes, span recording is an
O(1) list append under the GIL, and a *disabled* tracer costs exactly one
predicate per instrumentation site.  See docs/OBSERVABILITY.md.
"""

from .histogram import LatencyHistogram
from .registry import REGISTRY, Gauge, MetricsRegistry, get_registry
from .trace import (Tracer, enable_tracing, export_chrome_trace,
                    new_span_id, record_span, span, spanned_first_call,
                    tracer)
from .events import recorder
from .cluster import ClusterView, StragglerDetector
from .capacity import (CapacityModel, DriftAuditor, achieved_mfu,
                       stage_flops_bytes)
from .report import ObsReporter, start_prom_server
from .journal import (read_journal, read_process_journals, start_journal,
                      stop_journal)
from .postmortem import collect as collect_postmortem, maybe_autopsy
from .profile import (DECODE_DISPATCH_PHASES, DECODE_PHASES,
                      DECODE_STATS_PHASES, DOOR_PHASES,
                      ENGINE_DISPATCH_PHASES, ENGINE_LOOP_PHASES,
                      ENGINE_PHASES, SETUP_PHASES, SPAN_LAYERS,
                      MemoryWatcher, PauseWatcher, ProfileSession,
                      RecompileWatcher, pause_watcher, recompile_watcher,
                      setup_breakdown, setup_log)

__all__ = [
    "LatencyHistogram",
    "MetricsRegistry", "REGISTRY", "get_registry", "Gauge",
    "Tracer", "tracer", "enable_tracing", "export_chrome_trace",
    "new_span_id", "span", "record_span", "spanned_first_call",
    "recorder",
    "ClusterView", "StragglerDetector",
    "CapacityModel", "DriftAuditor", "achieved_mfu", "stage_flops_bytes",
    "ObsReporter", "start_prom_server",
    "start_journal", "stop_journal", "read_journal",
    "read_process_journals",
    "collect_postmortem", "maybe_autopsy",
    "ENGINE_PHASES", "ENGINE_DISPATCH_PHASES", "ENGINE_LOOP_PHASES",
    "DECODE_PHASES", "DECODE_DISPATCH_PHASES", "DECODE_STATS_PHASES",
    "DOOR_PHASES", "SETUP_PHASES", "SPAN_LAYERS", "ProfileSession",
    "RecompileWatcher", "recompile_watcher", "MemoryWatcher",
    "PauseWatcher", "pause_watcher", "setup_breakdown", "setup_log",
]
