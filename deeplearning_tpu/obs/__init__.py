"""Run-wide observability: span timeline, compile/HBM telemetry, crash
flight recorder.

Three pieces, one policy (README "Observability policy"):

- ``spans``  — thread-aware ring-buffered host span tracer; emits
  Chrome trace-event JSON (``trace.json``); run-level ``phase``s of the
  set-up that are always recorded; ``last()`` keeps a run's ring readable
  after ``disable()``. Near-zero cost when disabled.
- ``xla``    — compile telemetry (seconds / FLOPs / peak HBM /
  persistent-cache hit per lowering, via ``tracked_compile``) and
  device-memory watermarking (``hbm_snapshot`` + the ``HbmWatermark``
  sampler thread).
- ``flight`` — bounded ring of recent structured events (step metric
  snapshots, feed stats, retraces, compiles, serve rejections) dumped
  to ``flightrec.json`` on divergence abort, uncaught trainer
  exception, or SIGTERM.
- ``metrics`` — sync-free Counter/Gauge/Histogram registry with
  Prometheus text exposition (``/metrics`` on every replica via
  ``MetricsServer``) and a JSON snapshot. Same disabled-path budget
  as spans: the hot-path helpers are one ``is None`` check when off.
- ``threads`` — the thread spawn registry: every background thread in
  the runtime is created via ``threads.spawn(target, name=...)`` so
  the concurrency linter (DLT204) and the strict-mode thread sanitizer
  know every entry point. Stdlib-only.
- ``fleet``  — scraper/aggregator over N replica ``/metrics``
  endpoints: rollups (summed QPS, max e2e p99, queue depth, replica
  status counts), SLO breach flight events, ``fleet.jsonl``
  timeseries. Pure stdlib; imported lazily (``from .obs import
  fleet``) since only supervisors need it.

``tools/obs_report.py`` renders a run directory (metrics.jsonl +
trace.json + flightrec.json + fleet.jsonl) into the phase-time report
every ROADMAP on-chip calibration item consumes;
``tools/trace_merge.py`` joins per-replica trace.json dumps into one
fleet timeline.
"""

from . import flight, metrics, spans, threads, xla
from .flight import FlightRecorder
from .metrics import MetricsRegistry, MetricsServer
from .spans import SpanTracer, span, step_span
from .xla import HbmWatermark, hbm_snapshot, tracked_compile

__all__ = ["spans", "xla", "flight", "metrics", "threads", "SpanTracer",
           "span", "step_span", "FlightRecorder",
           "HbmWatermark", "hbm_snapshot", "tracked_compile",
           "MetricsRegistry", "MetricsServer"]
