"""Unified observability layer (docs/observability.md).

One place the whole stack reports through, replacing the per-subsystem
patchwork (`Timed` log lines, the serving counter dict, hand-rolled
``perf_counter`` pairs in descent, the unlocked ``SCORE_KERNEL_STATS``
global):

* ``metrics``  — :class:`MetricsRegistry` of named counters/gauges/
  histograms with JSON snapshots and Prometheus text exposition
  (``GET /metrics?format=prom``);
* ``trace``    — :class:`trace_span`/:func:`instant` emitting Chrome
  trace-event JSON (Perfetto-loadable) with propagated trace ids, threaded
  through ingest, coordinate descent, optimizer solves, and the serving
  path (``--trace-out`` on every driver);
* ``retrace``  — jit-compilation sentinel: per-kernel trace counters and a
  loud warning (log + trace event) when a hot-path kernel retraces after
  warmup, plus device-memory watermark gauges.

Both hooks follow ``faults.fault_point``'s cost model: one module-global
read when inactive, so the instrumentation is always-on in production code.

The CONSUMERS of these artifacts live in ``photon_tpu.obs.analysis``
(imported on demand, not re-exported here): the trace-timeline analyzer
(``python -m photon_tpu.obs.analysis``), the backend-aware bench
regression gate (``scripts/bench_compare.py``), and the declarative SLO
watchdog (``obs.analysis.slo``) evaluated at serving flushes, supervisor
heartbeats, and bench end. ``photon_tpu.obs.live`` (same on-demand rule —
it imports the analysis layer) is the streaming fleet view behind
``python -m photon_tpu.cli.obs_driver``: the run-report detector folded
online over a live telemetry dir, served at ``GET /fleet``.
"""
from photon_tpu.obs.metrics import (
    Counter,
    Gauge,
    HistogramMetric,
    MetricsRegistry,
    REGISTRY,
    get_registry,
)
from photon_tpu.obs.trace import (
    ANCHOR_EVENT,
    TailSampler,
    TraceCollector,
    current_trace_id,
    device_wait,
    install_tail_sampler,
    instant,
    new_trace_id,
    process_role,
    recent_trees,
    set_process_role,
    start_tracing,
    stop_tracing,
    suspend_tracing,
    tail_sampler,
    trace_context,
    trace_span,
    tracing,
    tracing_active,
    uninstall_tail_sampler,
)
from photon_tpu.obs import retrace

__all__ = [
    "ANCHOR_EVENT",
    "Counter",
    "Gauge",
    "HistogramMetric",
    "MetricsRegistry",
    "REGISTRY",
    "get_registry",
    "TailSampler",
    "TraceCollector",
    "current_trace_id",
    "device_wait",
    "install_tail_sampler",
    "instant",
    "new_trace_id",
    "process_role",
    "recent_trees",
    "retrace",
    "set_process_role",
    "start_tracing",
    "stop_tracing",
    "suspend_tracing",
    "tail_sampler",
    "trace_context",
    "trace_span",
    "tracing",
    "tracing_active",
    "uninstall_tail_sampler",
]
