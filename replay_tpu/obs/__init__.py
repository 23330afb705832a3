"""Run-telemetry subsystem: trainer events, step timing, MFU, memory, compiles.

The reference stack gets training observability for free from PyTorch Lightning
(loggers, progress bars, callbacks — replay/nn/lightning/module.py:14-120 wires
them); this JAX stack has no Lightning, so the trainer emits structured
:class:`TrainerEvent` records to pluggable :class:`RunLogger` sinks instead,
and a collectors layer measures what Lightning never could: jit retraces
(:class:`CompileTracker`), device memory (:class:`MemoryMonitor`), steady-state
throughput (:class:`StepTelemetry`) and achieved-vs-peak FLOPs (:mod:`.mfu`).
:mod:`.trace` adds host-side span tracing + goodput accounting (where does
wall-clock go BETWEEN steps — ``trace.json`` + per-epoch phase fractions),
:mod:`.health` computes in-graph model-health diagnostics (per-group norms and
update ratios, activation stats, attention entropy, the ``HealthWatcher``
early warning), :mod:`.roofline` classifies every
compiled program memory- vs compute-bound against the chip's peak FLOPs/
bandwidth tables (with HBM footprint + collective-bytes introspection via
:mod:`replay_tpu.parallel.introspect`), and :mod:`.report` is the run-report
CLI over the artifacts (``python -m replay_tpu.obs.report <run_dir>``).
The LIVE half (docs/observability.md): :mod:`.metrics` keeps a thread-safe
registry (counters/gauges/histograms) bridged from the same event stream,
:mod:`.exporter` serves it as a scrapeable Prometheus ``/metrics`` endpoint
(+ ``/snapshot`` JSON), and :mod:`.slo` evaluates declarative threshold rules
at step/batch cadence, emitting ``on_slo_violation`` through the same sinks.
The POST-MORTEM half: :mod:`.blackbox` is the SIGKILL-proof flight recorder
(an mmap ring every sink family bridges into; ``read_flight`` tolerates the
torn final record), ``obs.report --postmortem`` reconstructs a dead fleet's
last-known-activity timelines from rings + event shards + checkpoint
sidecars, and :mod:`.federate` merges N per-process ``/snapshot`` exporters
into ONE fleet-level ``/metrics``.
Beyond-parity — SURVEY.md §5.
"""

from .blackbox import BlackboxLogger, FlightLog, FlightRecorder, read_flight
from .collectors import CompileTracker, MemoryMonitor, StepTelemetry
from .federate import FleetFederator, federate_snapshots, scrape_snapshot
from .health import HealthConfig, HealthWatcher, flatten_health, health_metrics
from .events import (
    ConsoleLogger,
    JsonlLogger,
    MultiLogger,
    RunLogger,
    TensorBoardLogger,
    TrainerEvent,
)
from .exporter import MetricsExporter
from .metrics import MetricsLogger, MetricsRegistry
from .quality import (
    QUALITY_SLOS,
    DriftDetector,
    PopularityDescriptor,
    QualityMonitor,
    canary_quality_rules,
    population_stability_index,
    prequential_scores,
)
from .slo import SLORule, SLOWatchdog
from .mfu import (
    PEAK_BF16_TFLOPS,
    cost_analysis,
    flops_per_step,
    mfu,
    peak_tflops,
    program_costs,
)
from .roofline import (
    PEAK_HBM_GBPS,
    analyze_program,
    classify,
    of_ceiling,
    peak_bandwidth,
)
from .trace import (
    GOODPUT_SPANS,
    REQUEST_HOP_SPANS,
    SERVE_GOODPUT_SPANS,
    TraceContext,
    Tracer,
    chunk_stage_log,
    goodput_breakdown,
    lifecycle_span,
    merge_traces,
    stage,
    startup_log,
    tail_attribution,
    traced_iterator,
)

__all__ = [
    "BlackboxLogger",
    "CompileTracker",
    "ConsoleLogger",
    "DriftDetector",
    "FleetFederator",
    "FlightLog",
    "FlightRecorder",
    "GOODPUT_SPANS",
    "HealthConfig",
    "HealthWatcher",
    "JsonlLogger",
    "MemoryMonitor",
    "MetricsExporter",
    "MetricsLogger",
    "MetricsRegistry",
    "MultiLogger",
    "REQUEST_HOP_SPANS",
    "SLORule",
    "SLOWatchdog",
    "PEAK_BF16_TFLOPS",
    "PEAK_HBM_GBPS",
    "PopularityDescriptor",
    "QUALITY_SLOS",
    "QualityMonitor",
    "RunLogger",
    "SERVE_GOODPUT_SPANS",
    "StepTelemetry",
    "TensorBoardLogger",
    "TraceContext",
    "Tracer",
    "TrainerEvent",
    "analyze_program",
    "canary_quality_rules",
    "chunk_stage_log",
    "classify",
    "cost_analysis",
    "federate_snapshots",
    "flatten_health",
    "flops_per_step",
    "goodput_breakdown",
    "health_metrics",
    "lifecycle_span",
    "merge_traces",
    "mfu",
    "of_ceiling",
    "peak_bandwidth",
    "peak_tflops",
    "population_stability_index",
    "prequential_scores",
    "program_costs",
    "read_flight",
    "scrape_snapshot",
    "stage",
    "startup_log",
    "tail_attribution",
    "traced_iterator",
]
