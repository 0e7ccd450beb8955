"""Compile + device-memory telemetry.

Every AOT lowering in the library (Trainer.precompile, the serving
engine's bucket warmup, ``utils.profiling.compiled_flops``) funnels
through ``tracked_compile``: the compile is timed, XLA's
``cost_analysis`` (FLOPs) and ``memory_analysis`` (peak HBM) are read
off the executable, the persistent-cache hit/miss verdict is JAX's own
(its monitoring events), and the event lands in three places at once — the
bounded ``compile_events()`` ring (the ``/stats`` surface), the span
timeline (a ``compile/<name>`` span with FLOPs/HBM args), and the
flight recorder (so a crash dump shows what was compiled when).

HBM watermarking: ``hbm_snapshot()`` reads ``device.memory_stats()``
(TPU runtimes report ``bytes_in_use``/``peak_bytes_in_use`` and, for
the region a running program's temporaries live in,
``bytes_reserved``/``peak_bytes_reserved``; CPU returns nothing) plus a
``jax.live_arrays()`` census — count and total bytes of every live
buffer the process holds. ``HbmWatermark`` samples
that snapshot from its own thread ("obs-metrics") on an interval,
tracking run-peak values; its samples are spans, so the timeline shows
memory next to the phases that allocated it.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Dict, List, Optional

from . import flight, metrics, spans
from . import threads as obs_threads

__all__ = ["tracked_compile", "compile_events", "compile_stats",
           "jax_cache_counts", "memory_analysis_dict", "hbm_snapshot",
           "HbmWatermark", "set_hbm_alert_frac"]

# bounded ring of compile-event dicts (module-wide: compiles are rare
# and the ring is the natural join point for /stats and obs_report)
_EVENTS: collections.deque = collections.deque(maxlen=512)
_EVENTS_LOCK = threading.Lock()

_MEM_FIELDS = ("temp_size_in_bytes", "argument_size_in_bytes",
               "output_size_in_bytes", "alias_size_in_bytes",
               "generated_code_size_in_bytes")


def memory_analysis_dict(compiled) -> Dict[str, float]:
    """``Compiled.memory_analysis()`` as a plain dict (missing fields and
    backends without the analysis yield ``{}`` — never raises)."""
    try:
        mem = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 - analysis is backend-optional
        return {}
    if mem is None:
        return {}
    out: Dict[str, float] = {}
    for field in _MEM_FIELDS:
        val = getattr(mem, field, None)
        if val is not None:
            out[field] = float(val)
    if out:
        # the executable's device-memory high-water mark: resident
        # args + outputs + scratch (aliased bytes counted once)
        out["peak_hbm_bytes"] = (
            out.get("argument_size_in_bytes", 0.0)
            + out.get("output_size_in_bytes", 0.0)
            + out.get("temp_size_in_bytes", 0.0)
            - out.get("alias_size_in_bytes", 0.0))
    return out


# JAX's own verdicts on its persistent compilation cache. Its monitoring
# events fire synchronously on the compiling thread, so process totals
# serve ``jax_cache_counts`` and a per-thread hit count tells
# ``tracked_compile`` about exactly its own compile. (Counting files in
# the cache directory does not: with a size cap set the cache evicts, and
# a 30 s compile on the chip was once reported as a hit.)
_JAX_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
_jax_cache_totals = dict.fromkeys(_JAX_CACHE_EVENTS.values(), 0)
_jax_cache_thread = threading.local()
_jax_cache_listening = False


def _on_jax_event(event: str, **_) -> None:
    key = _JAX_CACHE_EVENTS.get(event)
    if key is None:
        return
    with _EVENTS_LOCK:
        _jax_cache_totals[key] += 1
    if key == "hits":
        _jax_cache_thread.hits = _thread_cache_hits() + 1


def _thread_cache_hits() -> int:
    return getattr(_jax_cache_thread, "hits", 0)


def _listen_to_jax_cache() -> None:
    global _jax_cache_listening
    with _EVENTS_LOCK:
        if _jax_cache_listening:
            return
        _jax_cache_listening = True
    import jax.monitoring
    jax.monitoring.register_event_listener(_on_jax_event)


def jax_cache_counts() -> Dict[str, int]:
    """Process totals of {requests, hits, misses} as JAX reports them,
    counted from the first call of this or of ``tracked_compile``."""
    _listen_to_jax_cache()
    with _EVENTS_LOCK:
        return dict(_jax_cache_totals)


def tracked_compile(lowered, name: str):
    """``lowered.compile()`` with telemetry: returns the executable and
    records {fn, seconds, flops, peak_hbm_bytes, cache_hit} everywhere
    the observability stack looks. Never raises past the compile itself
    — a telemetry failure must not fail a warmup path."""
    from ..core.compile_cache import active_cache_dir
    _listen_to_jax_cache()
    hits_before = _thread_cache_hits()
    t0 = time.perf_counter()
    compiled = lowered.compile()
    seconds = time.perf_counter() - t0
    try:
        from ..utils.profiling import cost_analysis_dict
        cost = cost_analysis_dict(compiled)
        mem = memory_analysis_dict(compiled)
        cache_hit = (None if active_cache_dir() is None
                     else _thread_cache_hits() > hits_before)
        event = {
            "fn": name,
            "seconds": round(seconds, 4),
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "peak_hbm_bytes": mem.get("peak_hbm_bytes", 0.0),
            "cache_hit": cache_hit,
            "time": time.time(),
        }
        with _EVENTS_LOCK:
            _EVENTS.append(event)
        tracer = spans.get_tracer()
        if tracer is not None:
            tracer.record(f"compile/{name}", t0, seconds,
                          {k: event[k] for k in
                           ("seconds", "flops", "peak_hbm_bytes",
                            "cache_hit")})
        flight.record("compile", **event)
        metrics.inc("dltpu_compiles_total")
        metrics.inc("dltpu_compile_seconds_total", seconds)
        if cache_hit:
            metrics.inc("dltpu_compile_cache_hits_total")
    except Exception:  # noqa: BLE001 - telemetry never fails a compile
        pass
    return compiled


def compile_events(last: Optional[int] = None) -> List[Dict[str, Any]]:
    with _EVENTS_LOCK:
        events = list(_EVENTS)
    return events if last is None else events[-last:]


def compile_stats() -> Dict[str, float]:
    """Aggregate view for /stats and bench rows."""
    events = compile_events()
    hits = sum(1 for e in events if e.get("cache_hit"))
    return {
        "compiles": float(len(events)),
        "compile_seconds_total": round(
            sum(e["seconds"] for e in events), 4),
        "compile_cache_hits": float(hits),
        "compile_peak_hbm_bytes": max(
            (e["peak_hbm_bytes"] for e in events), default=0.0),
    }


def clear_compile_events() -> None:
    with _EVENTS_LOCK:
        _EVENTS.clear()


# ------------------------------------------------------------- memory
# ROADMAP calibration-debt note: memory_stats() field sets vary by
# device generation (v4 lacks some of what v5 reports, CPU reports
# nothing), so every field is individually optional and individually
# int-converted — one odd field must not drop the whole entry.
_HBM_FIELDS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
               "largest_alloc_size", "bytes_reserved",
               "peak_bytes_reserved", "pool_bytes", "num_allocs")

# alert when bytes_in_use crosses this fraction of bytes_limit (None =
# off). Process-wide because hbm_snapshot is called from crash dumps and
# sampler threads that have no config handle.
_ALERT_FRAC: Optional[float] = None
_ALERTED: set = set()          # device ids already alerted (edge-trigger)


def set_hbm_alert_frac(frac: Optional[float]) -> Optional[float]:
    """Configure (or disable, with None) the HBM usage alert threshold;
    returns the previous value. The Trainer wires its ``hbm_alert_frac``
    kwarg here; ``DLTPU_HBM_ALERT_FRAC`` seeds it for bare scripts."""
    global _ALERT_FRAC
    previous = _ALERT_FRAC
    _ALERT_FRAC = None if frac is None else float(frac)
    _ALERTED.clear()
    return previous


def _env_alert_frac() -> Optional[float]:
    import os
    raw = os.environ.get("DLTPU_HBM_ALERT_FRAC")
    if not raw:
        return None
    try:
        return float(raw)
    except ValueError:
        return None


def _mem_entry(dev, stats, alert_frac: Optional[float]) -> Dict[str, Any]:
    """One device's snapshot entry from a raw memory_stats() dict, with
    per-field guards and the optional usage-fraction alert."""
    entry: Dict[str, Any] = {"id": dev.id,
                             "kind": getattr(dev, "device_kind", "")}
    if not stats:
        return entry
    for key in _HBM_FIELDS:
        if key in stats:
            try:
                entry[key] = int(stats[key])
            except (TypeError, ValueError):
                pass           # generation reports a non-numeric field
    # On the v5e's client ``peak_bytes_in_use`` leaves a running step's
    # temporaries out: they live in a region of their own that
    # ``peak_bytes_reserved`` counts (PERF.md §5: 1.86 GB in use beside
    # 7.38 GB reserved for the ViT-B/16 step). The device's peak is the sum
    # of the two, an upper bound as the peaks need not coincide; where the
    # client reports no reserved region it is the in-use peak alone.
    if "peak_bytes_in_use" in entry:
        entry["peak_bytes"] = (entry["peak_bytes_in_use"]
                               + entry.get("peak_bytes_reserved", 0))
    in_use, limit = entry.get("bytes_in_use"), entry.get("bytes_limit")
    if in_use is not None and limit:
        frac = in_use / limit
        entry["usage_frac"] = round(frac, 4)
        if alert_frac is not None and frac >= alert_frac:
            entry["alert"] = {"threshold_frac": alert_frac,
                              "usage_frac": round(frac, 4)}
            if dev.id not in _ALERTED:     # edge-trigger: once per device
                _ALERTED.add(dev.id)
                flight.record("hbm_alert", device=dev.id,
                              usage_frac=round(frac, 4),
                              threshold_frac=alert_frac,
                              bytes_in_use=in_use, bytes_limit=limit)
        elif alert_frac is not None:
            _ALERTED.discard(dev.id)       # re-arm once usage recedes
    return entry


def hbm_snapshot(alert_frac: Optional[float] = None) -> Dict[str, Any]:
    """One point-in-time device-memory reading; cheap enough to take at
    crash time and from the sampler thread. Fields that a backend or
    device generation does not report are simply absent. When an alert
    fraction is configured (argument > ``set_hbm_alert_frac`` >
    ``DLTPU_HBM_ALERT_FRAC``), a device crossing it gets an ``alert``
    sub-dict and an edge-triggered ``hbm_alert`` flight event."""
    if alert_frac is None:
        alert_frac = _ALERT_FRAC if _ALERT_FRAC is not None \
            else _env_alert_frac()
    snap: Dict[str, Any] = {"time": time.time()}
    try:
        import jax
        devices = []
        for d in jax.devices():
            try:
                stats = d.memory_stats()
            except Exception:  # noqa: BLE001 - CPU backends raise/None
                stats = None
            devices.append(_mem_entry(d, stats, alert_frac))
        snap["devices"] = devices
        arrs = jax.live_arrays()
        snap["live_arrays"] = {
            "count": len(arrs),
            "nbytes": int(sum(getattr(a, "nbytes", 0) for a in arrs)),
        }
    except Exception:  # noqa: BLE001 - snapshot is best-effort
        pass
    return snap


class HbmWatermark:
    """Background HBM sampler: one daemon thread ("obs-metrics") taking
    ``hbm_snapshot()`` every ``interval_s``, keeping run-peak watermarks
    and emitting each sample as a span from its own thread — the third
    lane of the trace timeline next to the hot loop and the feed worker.

    An immediate first sample on ``start()`` guarantees even a 5-step
    smoke run records at least one memory point."""

    def __init__(self, interval_s: float = 0.5,
                 alert_frac: Optional[float] = None):
        self.interval_s = max(float(interval_s), 0.01)
        self.alert_frac = alert_frac
        self.samples = 0
        self.peak_live_bytes = 0
        self.peak_bytes_in_use = 0
        self.peak_bytes = 0          # in use + reserved, see _mem_entry
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _sample(self) -> None:
        t0 = time.perf_counter()
        snap = hbm_snapshot(alert_frac=self.alert_frac)
        self.samples += 1
        live = snap.get("live_arrays", {}).get("nbytes", 0)
        self.peak_live_bytes = max(self.peak_live_bytes, live)
        for dev in snap.get("devices", []):
            in_use = dev.get("bytes_in_use", 0)
            self.peak_bytes_in_use = max(self.peak_bytes_in_use, in_use)
            self.peak_bytes = max(self.peak_bytes,
                                  dev.get("peak_bytes", in_use))
        tracer = spans.get_tracer()
        if tracer is not None:
            tracer.record("hbm_sample", t0,
                          time.perf_counter() - t0,
                          {"live_bytes": live,
                           "live_count":
                               snap.get("live_arrays", {}).get("count", 0),
                           "peak_live_bytes": self.peak_live_bytes})
        metrics.set_gauge("dltpu_hbm_live_bytes", float(live))
        metrics.set_gauge("dltpu_hbm_peak_live_bytes",
                          float(self.peak_live_bytes))
        metrics.set_gauge("dltpu_hbm_peak_bytes_in_use",
                          float(self.peak_bytes_in_use))
        metrics.set_gauge("dltpu_hbm_peak_bytes", float(self.peak_bytes))

    def _run(self) -> None:
        self._sample()                       # guaranteed first point
        while not self._stop.wait(self.interval_s):
            try:
                self._sample()
            except Exception:  # noqa: BLE001 - sampling is best-effort
                pass

    def start(self) -> "HbmWatermark":
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = obs_threads.spawn(
                self._run, name="obs-metrics", daemon=True)
        return self

    def stop(self, timeout: float = 2.0) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout)

    def watermark(self) -> Dict[str, float]:
        return {
            "hbm_samples": float(self.samples),
            "peak_live_bytes": float(self.peak_live_bytes),
            "peak_bytes_in_use": float(self.peak_bytes_in_use),
            "peak_bytes": float(self.peak_bytes),
        }

    def __enter__(self) -> "HbmWatermark":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()
