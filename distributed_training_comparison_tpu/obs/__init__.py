"""Observability: the run-event bus, span tracing, and the flight recorder.

Four generations of ad-hoc telemetry preceded this package — goodput
records (PR 2), health events (PR 3), the step-time breakdown (PR 4), and
the serve metrics — each with its own schema, file, and report tool, and
none able to answer "what was every thread of this run doing at second T
of attempt 3".  ``obs`` is the one layer they all now report through:

- ``bus.py``   — the **run-event bus**: one append-only ``events.jsonl``
  per attempt with a single versioned schema (run_id / attempt /
  process_index / wall + monotonic timestamps / kind / payload), plus the
  bounded in-memory ring the **flight recorder** dumps to
  ``crash_dump.json`` on abort or unhandled exception;
- ``spans.py`` — **host-side span tracing**: a nestable
  ``span("epoch")`` context manager recording begin/end pairs on every
  thread (trainer loop, ``DevicePrefetcher`` producer, the async
  checkpoint writer), exported as Chrome-trace/Perfetto JSON so one file
  shows compute, staging, and checkpointing overlapping in time.  Every
  span is a node of a tree (``id``, ``parent``, ``epoch``) and also a
  ``jax.profiler.TraceAnnotation``, so any profiler capture carries the
  host spans on its own clock beside the device's ops — one clock, nothing
  to join (``benchmark/harness/host_spans.py`` reads them there);
- ``metrics.py`` — **per-step metrics with a sampling budget**: typed
  counter/gauge/log-bucket-histogram accumulators the trainer records
  into every step, flushed as bounded periodic ``metrics`` bus events
  whose sketches merge associatively across flushes, hosts, and attempts;
- ``blackbox.py`` — the **SIGKILL-surviving flight recorder**: an mmap'd
  fixed-slot ring file per process mirroring every emit
  (torn-page-tolerant decode), pulled by the supervisor after every
  attempt into one cross-host ``blackbox.json`` under the ckpt root;
- ``heartbeat.py`` — **liveness**: bounded-cadence per-process
  ``heartbeat`` events, the supervisor-side tracker that classifies a
  lagging host as slow vs dead (``stall`` events before the collective
  wedges), and the fleet watcher thread that tails the event files live;
- ``straggler.py`` — **cross-host attribution**: merge every host's
  step-phase sketches and score each host's p95 against the rest of the
  fleet (median/MAD, leave-one-out), emitting ``straggler`` events that
  name host + phase;
- ``resource.py`` — device HBM (``memory_stats`` guarded through
  ``_compat``), host RSS, open fds, and ckpt-root disk-free gauges,
  sampled once per metric flush;
- ``exporter.py`` — an **OpenMetrics** ``/metrics`` endpoint per process
  (``--metrics-port``) rendering the live registry, heartbeat ages, and
  alert states; the same renderer serves ``run_report
  --export-openmetrics`` offline;
- ``alerts.py`` — declarative ``--alert`` rules (e.g.
  ``serve/latency_s:p99>0.25:for=3``; fleet aggregates via
  ``sum(...)``/``max(...)``, supervisor-evaluated) over flushed metric
  events and heartbeats, with hysteresis and firing/``resolved``
  ``alert`` events ``run_report --alerts`` gates CI on;
- ``compilation.py`` — **compiler & memory observability**: every jit
  lowering/AOT compile in the train runners and the serve engine emits a
  registered ``compile`` event (stable cross-process fingerprint,
  compile wall time, persistent-cache hit/miss, HLO cost/memory
  analysis), ``compile/*`` metrics feed the exporter and ``--alert``
  rules, a recompilation sentinel flags post-warmup compiles (serve
  bucket churn, elastic reshapes), and per-executable dispatch sketches
  let ``run_report --compute`` reconstruct measured MFU offline.

The process holds ONE current bus and ONE current span recorder
(``configure`` installs them; ``emit``/``span`` reach them from any
module without plumbing).  Before a Trainer binds the bus to its version
dir, events accumulate in memory and flush on bind — nothing emitted
during construction is lost.  The default, never-configured bus keeps
only the ring: library embedders that never call ``configure`` pay one
deque append per event and write no files.

``tools/run_report.py`` merges ``events*.jsonl`` across attempts and
hosts into one timeline + summary and validates captures (``--check``).
"""

from __future__ import annotations

from .blackbox import (
    BLACKBOX_NAME,
    MmapRing,
    collect_black_box,
    decode_ring,
    find_rings,
    ring_filename,
)
from .alerts import (
    ALERT_KIND,
    AlertEngine,
    AlertRule,
    AlertSpecError,
    alert_timeline,
    final_states,
    parse_alert_specs,
)
from .compilation import (
    COMPILE_KIND,
    PEAK_FLOPS_BY_DEVICE_KIND,
    CompileMonitor,
    ExecutableRecord,
    fingerprint_of,
    peak_flops_for,
    signature_fingerprint,
)
from .bus import (
    ATTEMPT_ENV,
    CRASH_DUMP_NAME,
    EVENTS_NAME,
    KNOWN_KINDS,
    RUN_ID_ENV,
    SCHEMA_VERSION,
    EventBus,
    configure,
    crash_dump_filename,
    current_bus,
    emit,
    events_filename,
    load_events,
    new_run_id,
    register_kind,
    reset,
    validate_event,
)
from .exporter import (
    MetricsExporter,
    openmetrics_name,
    render_openmetrics,
    start_exporter,
)
from .heartbeat import (
    HEARTBEAT_KIND,
    STALL_KIND,
    EventTailer,
    FleetWatcher,
    HeartbeatEmitter,
    LivenessTracker,
)
from .metrics import (
    METRICS_KIND,
    Counter,
    Gauge,
    Histogram,
    MetricRegistry,
    histogram_quantile,
    histogram_summary,
    merge_histograms,
    merge_metric_events,
)
from .reqtrace import (
    TRACE_KIND,
    RequestTracer,
    TraceContext,
    WorkerTraceRing,
)
from .resource import ResourceSampler
from .spans import (
    SpanRecorder,
    chrome_trace,
    current_recorder,
    open_span,
    set_recorder,
    span,
    trace_filename,
    write_chrome_trace,
)
from .straggler import (
    STRAGGLER_KIND,
    emit_straggler_events,
    host_phase_table,
    straggler_findings,
)
from . import straggler  # noqa: F401 (run_report renders its table)

__all__ = [
    "SCHEMA_VERSION",
    "EVENTS_NAME",
    "CRASH_DUMP_NAME",
    "BLACKBOX_NAME",
    "METRICS_KIND",
    "HEARTBEAT_KIND",
    "STALL_KIND",
    "STRAGGLER_KIND",
    "ALERT_KIND",
    "COMPILE_KIND",
    "TRACE_KIND",
    "RequestTracer",
    "TraceContext",
    "WorkerTraceRing",
    "PEAK_FLOPS_BY_DEVICE_KIND",
    "CompileMonitor",
    "ExecutableRecord",
    "fingerprint_of",
    "peak_flops_for",
    "signature_fingerprint",
    "KNOWN_KINDS",
    "RUN_ID_ENV",
    "ATTEMPT_ENV",
    "AlertEngine",
    "AlertRule",
    "AlertSpecError",
    "alert_timeline",
    "final_states",
    "parse_alert_specs",
    "EventTailer",
    "FleetWatcher",
    "HeartbeatEmitter",
    "LivenessTracker",
    "MetricsExporter",
    "openmetrics_name",
    "render_openmetrics",
    "start_exporter",
    "register_kind",
    "ResourceSampler",
    "emit_straggler_events",
    "host_phase_table",
    "straggler_findings",
    "EventBus",
    "MmapRing",
    "collect_black_box",
    "configure",
    "crash_dump_filename",
    "current_bus",
    "decode_ring",
    "emit",
    "events_filename",
    "find_rings",
    "load_events",
    "new_run_id",
    "reset",
    "ring_filename",
    "validate_event",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricRegistry",
    "histogram_quantile",
    "histogram_summary",
    "merge_histograms",
    "merge_metric_events",
    "SpanRecorder",
    "chrome_trace",
    "current_recorder",
    "open_span",
    "set_recorder",
    "span",
    "trace_filename",
    "write_chrome_trace",
]
