"""Telemetry subsystem: metrics registry, stall watchdog, profiler
capture, flight recorder.

See `registry.py` for the metric model, `watchdog.py` for stall
detection, `profiling.py` for on-demand `jax.profiler` windows,
`tracing.py` for the flight recorder + per-batch lineage tracing, and
docs/OBSERVABILITY.md for the gauge -> pipeline-stage map.
"""

from torched_impala_tpu.telemetry.registry import (
    DEFAULT_MS_BUCKETS,
    NAME_RE,
    PREFIX,
    Counter,
    EwmaTimer,
    Gauge,
    Histogram,
    Registry,
    get_registry,
)
from torched_impala_tpu.telemetry.watchdog import (
    StallWatchdog,
    dump_thread_stacks,
)
from torched_impala_tpu.telemetry import excepthook as _excepthook

install_thread_excepthook = _excepthook.install
uninstall_thread_excepthook = _excepthook.uninstall
from torched_impala_tpu.telemetry.profiling import (
    ProfilerCapture,
    StepWindowProfiler,
    parse_profile_steps,
)
from torched_impala_tpu.telemetry.tracing import (
    FlightRecorder,
    get_recorder,
    install_sigusr2,
    mint_lineage_id,
    validate_chrome_trace,
)
from torched_impala_tpu.telemetry.aggregate import (
    LABEL_RE,
    SnapshotLane,
    SnapshotWriter,
    TelemetryAggregator,
    WorkerTelemetry,
    export_merged_trace,
    get_aggregator,
    merge_chrome_events,
    proc_label,
)
from torched_impala_tpu.telemetry.alerts import (
    AlertEngine,
    SloSpec,
    default_slo_specs,
)
from torched_impala_tpu.telemetry.health import (
    HEALTH_LOG_PREFIX,
    HealthMonitor,
    PostmortemWriter,
    health_slo_specs,
)
from torched_impala_tpu.telemetry.export import (
    MetricsExporter,
    metric_name,
    parse_openmetrics,
    to_openmetrics,
    write_metrics_file,
)

__all__ = [
    "DEFAULT_MS_BUCKETS",
    "NAME_RE",
    "PREFIX",
    "Counter",
    "EwmaTimer",
    "Gauge",
    "Histogram",
    "Registry",
    "get_registry",
    "StallWatchdog",
    "dump_thread_stacks",
    "install_thread_excepthook",
    "uninstall_thread_excepthook",
    "ProfilerCapture",
    "StepWindowProfiler",
    "parse_profile_steps",
    "FlightRecorder",
    "get_recorder",
    "install_sigusr2",
    "mint_lineage_id",
    "validate_chrome_trace",
    "LABEL_RE",
    "SnapshotLane",
    "SnapshotWriter",
    "TelemetryAggregator",
    "WorkerTelemetry",
    "export_merged_trace",
    "get_aggregator",
    "merge_chrome_events",
    "proc_label",
    "AlertEngine",
    "SloSpec",
    "default_slo_specs",
    "HEALTH_LOG_PREFIX",
    "HealthMonitor",
    "PostmortemWriter",
    "health_slo_specs",
    "MetricsExporter",
    "metric_name",
    "parse_openmetrics",
    "to_openmetrics",
    "write_metrics_file",
]
