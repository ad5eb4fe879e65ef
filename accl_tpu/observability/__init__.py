"""End-to-end observability: one record per call (record.py), which
the tracer's Perfetto export, the metrics registry both backends and
the bench harnesses publish into, and the always-on flight recorder
all read; the hang watchdog + health/
OpenMetrics surface, and the r14 performance observatory — cross-rank
critical-path attribution, the native-engine telemetry sampler, and
the continuous regression sentinel.  See docs/observability.md and
docs/debugging.md for usage."""

from .attribution import (  # noqa: F401
    attribute,
    estimate_clock_skew,
)
from .flight import (  # noqa: F401
    FlightRecorder,
    dump_all as dump_all_flight,
    enabled as flight_enabled,
    merge_flight_dumps,
)
from .health import (  # noqa: F401
    HEALTH_DEGRADED,
    HEALTH_HUNG,
    HEALTH_OK,
    HEALTH_SLOW,
    MetricsExporter,
    Watchdog,
    exporter_port,
    start_exporter,
    stop_exporter,
)
from .metrics import (  # noqa: F401
    LATENCY_BUCKETS_US,
    MetricsRegistry,
    busbw_factor,
    default_registry,
    dump_metrics,
    metric_help_for,
    payload_factor,
    size_bucket,
    validate_openmetrics,
)
from .record import CallRecord  # noqa: F401
from .sentinel import (  # noqa: F401
    Baseline,
    Sentinel,
)
from .telemetry import (  # noqa: F401
    ENGINE_STATS_FIELDS_V1,
    TelemetrySampler,
)
from .trace import (  # noqa: F401
    TraceCollector,
    collector,
    disable as disable_tracing,
    enable as enable_tracing,
    enabled as tracing_enabled,
    merge_trace_files,
    new_span,
    traced_window,
)
