"""Cluster-wide telemetry plane.

Five layers, all stdlib-only (importable from worker entry points
without pulling in jax):

* :mod:`~raydp_tpu.telemetry.spans` — structured spans with parent
  links and an in-process ring buffer, wired into the framework's hot
  paths (loader chunk staging, estimator epochs/steps, SPMD dispatch,
  DataFrame stages, master worker lifecycle).
* :mod:`~raydp_tpu.telemetry.propagation` — cross-process /
  cross-thread trace context: the driver mints a job context, the RPC
  envelope and worker launch env carry a ``traceparent``, and the
  ``current_context()`` / ``propagated(ctx)`` API parents producer and
  handler threads, so one ``fit()`` yields ONE trace across the gang.
* :mod:`~raydp_tpu.telemetry.shipping` — delta-encoded
  ``metrics.snapshot()`` payloads piggybacked on existing heartbeat
  RPCs; the master merges them into a per-worker cluster view that
  survives worker death (tombstoned final snapshots).
* :mod:`~raydp_tpu.telemetry.export` — the merged view as Prometheus
  text exposition v0.0.4 (optionally served at ``/metrics``), plus
  append-only per-process JSONL span shards under
  ``RAYDP_TPU_TELEMETRY_DIR``.
* :mod:`~raydp_tpu.telemetry.chrome_trace` /
  :mod:`~raydp_tpu.telemetry.analyze` — merge the shards into a
  Perfetto-loadable Chrome trace (clock-aligned), extract the critical
  path, and report per-rank step skew + data-wait vs compute
  (``python -m raydp_tpu.telemetry.analyze <dir>`` or
  ``Cluster.trace_report()``).

* :mod:`~raydp_tpu.telemetry.watchdog` /
  :mod:`~raydp_tpu.telemetry.flight_recorder` /
  :mod:`~raydp_tpu.telemetry.logs` — the health plane: in-flight-op
  stall detection shipped to ``Cluster.health_report()`` and served at
  ``/healthz``, a per-process crash flight recorder that dumps
  postmortem bundles (event tail + all-thread stacks), and
  trace-stamped JSONL structured logs.

* :mod:`~raydp_tpu.telemetry.device_profiler` — gang-coordinated
  ``jax.profiler`` capture merged into one Perfetto trace
  (``Cluster.capture_profile()`` / ``/debug/profile``), and
  NaN / step-regression anomaly sentinels.

* :mod:`~raydp_tpu.telemetry.accounting` /
  :mod:`~raydp_tpu.telemetry.events` — the job accounting plane: a
  :class:`JobContext` minted at workload roots and propagated like the
  traceparent, a usage ledger (chip-seconds, task-seconds, bytes
  moved) billed per job via :func:`add_usage` and exported as
  ``raydp_job_*`` families / ``usage_report()``, and a cluster event
  timeline (worker churn, gang lifecycle, preemption, checkpoints,
  sentinel trips) served at ``/debug/events`` and merged into the
  Perfetto trace (``python -m raydp_tpu.telemetry.events <dir>``).

* :mod:`~raydp_tpu.telemetry.timeseries` /
  :mod:`~raydp_tpu.telemetry.slo` /
  :mod:`~raydp_tpu.telemetry.dashboard` — the observability control
  plane: a driver-side bounded time-series store sampled from the
  merged registry at fixed cadence, declarative SLO objectives
  evaluated as multi-window burn rates (breach/recovery hysteresis,
  ``slo/breach`` auto-triage events, ``raydp_slo_*`` families), and
  the unified flywheel dashboard (``/debug/dashboard``,
  ``Cluster.dashboard_report()``,
  ``python -m raydp_tpu.telemetry.dashboard``).

Drivers pull the live aggregate with ``Cluster.metrics_snapshot()``
(works identically through ``raydp_tpu.connect`` client sessions).
See ``doc/telemetry.md``.
"""
from raydp_tpu.telemetry.chrome_trace import (
    load_span_records,
    to_chrome_trace,
    write_chrome_trace,
)
from raydp_tpu.telemetry.export import (
    DEBUG_PORT_ENV,
    METRICS_PORT_ENV,
    TELEMETRY_DIR_ENV,
    flush_spans,
    render_prometheus,
    serve_prometheus,
    telemetry_dir,
    write_events,
)
from raydp_tpu.telemetry import (
    accounting,
    dashboard,
    device_profiler,
    events,
    flight_recorder,
    logs,
    progress,
    slo,
    timeseries,
    watchdog,
)
from raydp_tpu.telemetry.accounting import (
    JOB_ENV,
    JobContext,
    add_usage,
    adopt_env_job,
    current_job,
    ensure_job,
    job_scope,
    mint_job,
    set_process_job,
    usage_report,
)
from raydp_tpu.telemetry.events import (
    EVENT_BUFFER_ENV,
    load_event_records,
    mttr_report,
)
from raydp_tpu.telemetry.device_profiler import (
    AnomalySentinel,
    capture_trace_archive,
    merge_rank_traces,
)
from raydp_tpu.telemetry.progress import (
    PROGRESS_LOG_ENV,
    STAGE_STATS_ENV,
    STATS_DIR_ENV,
    ProgressTracker,
    StageStats,
    StageStatsStore,
    stage_stats_enabled,
    stage_store,
)
from raydp_tpu.telemetry.flight_recorder import (
    POSTMORTEM_DIR_ENV,
    dump_bundle,
    latest_bundle,
    postmortem_dir,
)
from raydp_tpu.telemetry.watchdog import Watchdog, inflight
from raydp_tpu.telemetry.propagation import (
    TRACEPARENT_ENV,
    TraceContext,
    adopt_env_context,
    current_context,
    env_for_child,
    from_traceparent,
    mint_context,
    process_context,
    propagated,
    set_process_context,
    to_traceparent,
)
from raydp_tpu.telemetry.shipping import ClusterTelemetry, MetricsShipper
from raydp_tpu.telemetry.slo import Objective, SloConfig, SloEngine
from raydp_tpu.telemetry.spans import Span, SpanRecorder, event, recorder, span
from raydp_tpu.telemetry.timeseries import (
    TIMESERIES_ENV,
    TimeSeriesConfig,
    TimeSeriesSampler,
    TimeSeriesStore,
    timeseries_enabled,
)

__all__ = [
    "Span",
    "SpanRecorder",
    "TraceContext",
    "recorder",
    "span",
    "event",
    "MetricsShipper",
    "ClusterTelemetry",
    "TELEMETRY_DIR_ENV",
    "METRICS_PORT_ENV",
    "DEBUG_PORT_ENV",
    "POSTMORTEM_DIR_ENV",
    "TRACEPARENT_ENV",
    "JOB_ENV",
    "EVENT_BUFFER_ENV",
    "flight_recorder",
    "logs",
    "watchdog",
    "device_profiler",
    "accounting",
    "events",
    "dashboard",
    "slo",
    "timeseries",
    "TIMESERIES_ENV",
    "TimeSeriesConfig",
    "TimeSeriesStore",
    "TimeSeriesSampler",
    "timeseries_enabled",
    "Objective",
    "SloConfig",
    "SloEngine",
    "JobContext",
    "current_job",
    "job_scope",
    "mint_job",
    "ensure_job",
    "set_process_job",
    "adopt_env_job",
    "add_usage",
    "usage_report",
    "load_event_records",
    "mttr_report",
    "AnomalySentinel",
    "capture_trace_archive",
    "merge_rank_traces",
    "Watchdog",
    "inflight",
    "dump_bundle",
    "latest_bundle",
    "postmortem_dir",
    "telemetry_dir",
    "flush_spans",
    "write_events",
    "render_prometheus",
    "serve_prometheus",
    "current_context",
    "propagated",
    "set_process_context",
    "process_context",
    "mint_context",
    "adopt_env_context",
    "env_for_child",
    "to_traceparent",
    "from_traceparent",
    "load_span_records",
    "to_chrome_trace",
    "write_chrome_trace",
]
