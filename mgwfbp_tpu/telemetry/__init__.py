"""Run-observability subsystem: typed event stream, overlap-efficiency
accounting, Chrome-trace / Prometheus export, and the LIVE plane.

Every layer feeds one append-only, schema-versioned JSONL stream per run
(`telemetry/events.py`); `telemetry/phases.py` times the parts of a train
loop iteration onto the `step` records and into the profiler's trace;
`telemetry/overlap.py` turns per-group comm times
(trace-attributed or cost-model-predicted) into the paper's exposed-vs-
hidden accounting; `telemetry/export.py` renders the stream for Perfetto
and Prometheus (one metric registry shared with the live endpoint);
`telemetry/serve.py` serves /metrics, /healthz and /status per process
from an in-memory aggregator fed by the same stream;
`telemetry/drift.py` watches predicted-vs-measured cost-model residuals
and the multi-host straggler signal; `tools/telemetry_report.py` prints
the human summary.
"""

from mgwfbp_tpu.telemetry.drift import (
    DriftAlarm,
    DriftConfig,
    DriftDetector,
    StragglerDetector,
)
from mgwfbp_tpu.telemetry.health import (
    HealthAlarm,
    HealthConfig,
    HealthDetector,
)
from mgwfbp_tpu.telemetry.recorder import (
    FlightRecorder,
    list_bundles,
    read_bundle,
    tee_observers,
)
from mgwfbp_tpu.telemetry.events import (
    EVENT_SCHEMA_VERSION,
    EVENT_TYPES,
    EventWriter,
    events_of,
    find_stream_paths,
    read_event_set,
    read_events,
    stream_filename,
)
from mgwfbp_tpu.telemetry.fleet import (
    ChildScrape,
    FleetServer,
    fleet_status,
    render_fleet_metrics,
    scrape_fleet,
    start_fleet_server,
    write_fleet_sd,
)
from mgwfbp_tpu.telemetry.overlap import (
    GroupOverlap,
    OverlapSummary,
    attribute_overlap,
    group_comm_times,
    summarize,
)
from mgwfbp_tpu.telemetry.serve import (
    MetricsAggregator,
    TelemetryServer,
    start_metrics_server,
)

__all__ = [
    "DriftAlarm",
    "DriftConfig",
    "DriftDetector",
    "StragglerDetector",
    "HealthAlarm",
    "HealthConfig",
    "HealthDetector",
    "FlightRecorder",
    "list_bundles",
    "read_bundle",
    "tee_observers",
    "ChildScrape",
    "FleetServer",
    "fleet_status",
    "render_fleet_metrics",
    "scrape_fleet",
    "start_fleet_server",
    "write_fleet_sd",
    "MetricsAggregator",
    "TelemetryServer",
    "start_metrics_server",
    "EVENT_SCHEMA_VERSION",
    "EVENT_TYPES",
    "EventWriter",
    "events_of",
    "find_stream_paths",
    "read_event_set",
    "read_events",
    "stream_filename",
    "GroupOverlap",
    "OverlapSummary",
    "attribute_overlap",
    "group_comm_times",
    "summarize",
]
