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
from .registry import REGISTRY, Counter, Gauge, MetricsRegistry, get_registry
from .trace import (Tracer, enable_tracing, export_chrome_trace,
                    new_span_id, span, tracer, trace_context)
from .events import (EVENT_KINDS, FlightRecorder, merge_events,
                     recorder, validate_event)
from .events import emit as emit_event
from .attrib import (DoorAttribution, RequestAttribution,
                     attribute_request, attribute_sampled)
from .cluster import (ClusterView, StragglerDetector, StragglerFlag,
                      align_clock, estimate_clock_offset,
                      expected_stage_ms)
from .capacity import (CapacityModel, DriftAuditor, DriftFlag,
                       achieved_mfu, stage_flops_bytes)
from .report import ObsReporter, start_prom_server
from .journal import (JOURNAL_VERSION, JournalSpiller, JournalWriter,
                      active_journal, read_journal,
                      read_process_journals, start_journal, stop_journal)
from .postmortem import (BUNDLE_VERSION, collect as collect_postmortem,
                         maybe_autopsy)
from .profile import (DECODE_PHASES, DECODE_STATS_PHASES, DOOR_PHASES,
                      ENGINE_LOOP_PHASES, ENGINE_PHASES, NODE_PHASES,
                      SPAN_LAYERS,
                      MemoryWatcher, ProfileSession, RecompileWatcher,
                      device_memory_bytes, memory_watcher,
                      recompile_watcher)

__all__ = [
    "LatencyHistogram",
    "MetricsRegistry", "REGISTRY", "get_registry", "Counter", "Gauge",
    "Tracer", "tracer", "enable_tracing", "export_chrome_trace",
    "trace_context", "new_span_id", "span",
    "FlightRecorder", "recorder", "emit_event", "merge_events",
    "validate_event", "EVENT_KINDS",
    "RequestAttribution", "attribute_request", "attribute_sampled",
    "DoorAttribution",
    "ClusterView", "StragglerDetector", "StragglerFlag",
    "estimate_clock_offset", "align_clock", "expected_stage_ms",
    "CapacityModel", "DriftAuditor", "DriftFlag", "achieved_mfu",
    "stage_flops_bytes",
    "ObsReporter", "start_prom_server",
    "JOURNAL_VERSION", "JournalWriter", "JournalSpiller",
    "start_journal", "stop_journal", "active_journal",
    "read_journal", "read_process_journals",
    "BUNDLE_VERSION", "collect_postmortem", "maybe_autopsy",
    "NODE_PHASES", "ENGINE_PHASES", "ENGINE_LOOP_PHASES", "DECODE_PHASES",
    "DECODE_STATS_PHASES", "DOOR_PHASES", "SPAN_LAYERS", "ProfileSession",
    "RecompileWatcher", "recompile_watcher",
    "MemoryWatcher", "memory_watcher", "device_memory_bytes",
]
