"""Observability: tracing, typed metrics, exporters — over the
telemetry spine.

The reference Mosaic inherits Spark's UI and metrics system for free;
this package is the TPU reproduction's equivalent, grown from (and
backward-compatible with) `runtime/telemetry.py`'s flat event trail:

- **tracing** (`obs/trace.py`) — Dapper-style spans with
  ``trace_id``/``span_id``/``parent_id`` and explicit cross-thread
  propagation (:func:`current_context`/:func:`adopt_context`), so one
  serve request is ONE trace across admit → batch → dispatch →
  scatter-back, and one durable stream run is one trace across ring
  build → segments → snapshots → resume. Retry/escalation/watchdog/
  degradation events are stamped with the enclosing span's ids
  automatically;
- **metrics** (`obs/metrics.py`) — typed counters/gauges/histograms
  with labels (``serve.requests_shed{reason}``,
  ``join.cap_overflows{stage}``, ``stream.hbm_peak_bytes``,
  ``obs.compile_count{kind}``), fed by an event→metric bridge off the
  telemetry spine plus direct gauges where no event exists;
- **exporters** (`obs/export.py`) — JSONL trails, Chrome trace-event
  JSON (Perfetto-loadable; the host-side complement of the xprof
  device traces), Prometheus text exposition;
- **flight recorder** (`obs/recorder.py`) — an always-on bounded ring
  over the spine (``MOSAIC_RECORDER_N``) that auto-dumps on typed
  failures (RetryExhausted / StalledDeviceError / DegradedResult), so
  post-hoc diagnosis never requires a re-run;
- **timeline attribution** (`obs/timeline.py`) — interval
  reconstruction from span ``start_mono``/``seconds``, per-track
  gap/overlap, and the priority sweep that classifies lost wall time
  into {transfer, compile, queue_wait, host_callback, device, idle};
- **stage tables** (`obs/stages.py`) — programs register how to lower
  them again; a trace reader gets ``{module: {op: pip.*/stream.* stage}}``
  from the optimized HLO's metadata, so device ops carry the join's own
  stage names (nothing is lowered until a reader asks);
- **SLO monitor** (`obs/slo.py`) — the live ops plane's alerting core:
  sliding-window burn-rate evaluation over registered SLO specs
  (default set gated on ``MOSAIC_SLO_ENABLE``), breaches emitted as
  typed ``slo_violation`` events that trip the flight recorder;
- **health** (`obs/health.py`) — per-subsystem and per-tenant
  three-state health machine (healthy/degrading/unhealthy with
  hysteresis) over shed/retry/stall/degradation counters, exported as
  the ``obs.health{scope}`` gauge and consumed by the serve router's
  eviction order;
- **ops server** (`obs/ops_server.py`) — opt-in (``MOSAIC_OPS_PORT``)
  stdlib-HTTP pull endpoint serving Prometheus text plus the
  health/SLO snapshots.

Tools: `tools/trace_report.py` renders/diffs per-stage latency
breakdowns from trails; `tools/stall_report.py` decomposes a window of
wall time into stall classes; `tools/fleet_report.py` stitches many
processes' trails into one incarnation-linked timeline;
`tools/doctor.py` runs the known-failure-signature checks over
result artifacts and trails.

Importing this package registers the tracer, the metric bridge, the
flight recorder, the SLO monitor, and the health monitor with
`runtime/telemetry.py` (and starts the ops server iff
``MOSAIC_OPS_PORT`` is set); until then the runtime pays nothing for
any of them.
"""

from . import (
    export,
    health,
    metrics,
    ops_server,
    recorder,
    slo,
    stages,
    timeline,
    trace,
)
from .export import (
    chrome_trace,
    prometheus_text,
    read_trail,
    trace_summary,
    write_chrome_trace,
    write_jsonl,
)
from .metrics import (
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    Registry,
    counter,
    gauge,
    histogram,
    snapshot,
)
from .health import HealthMonitor
from .ops_server import OpsServer
from .recorder import RECORDER, FlightRecorder
from .slo import SLOMonitor, SLOSpec, evaluate_trail
from .trace import (
    Span,
    SpanContext,
    adopt_context,
    current_context,
    span,
    start_span,
)

metrics.install_bridge()
recorder.install()
slo.install()
health.install()
ops_server.maybe_start()

__all__ = [
    "FlightRecorder",
    "HealthMonitor",
    "OpsServer",
    "RECORDER",
    "REGISTRY",
    "SLOMonitor",
    "SLOSpec",
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "Span",
    "SpanContext",
    "adopt_context",
    "chrome_trace",
    "counter",
    "current_context",
    "evaluate_trail",
    "export",
    "gauge",
    "health",
    "histogram",
    "metrics",
    "ops_server",
    "prometheus_text",
    "read_trail",
    "recorder",
    "slo",
    "snapshot",
    "span",
    "stages",
    "start_span",
    "timeline",
    "trace",
    "trace_summary",
    "write_chrome_trace",
    "write_jsonl",
]
