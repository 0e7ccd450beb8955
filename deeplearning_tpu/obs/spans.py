"""Thread-aware ring-buffered host span tracer.

The run-wide timeline the ROADMAP's on-chip calibration items consume:
every layer that owns a thread (Trainer hot loop, DevicePrefetcher
worker, MicroBatcher dispatch, the obs HBM sampler) marks its phases
with ``span("data_wait")`` blocks, and the tracer serializes them as
Chrome trace-event JSON (``runs/<dir>/trace.json``) that Perfetto /
``chrome://tracing`` loads directly — one timeline across threads
instead of four disjoint counter surfaces.

Cost discipline (the hot-loop rule from README "Hot-loop sync policy"
extended to instrumentation):
- **Disabled** (the default): ``span(...)`` allocates one slotted object
  and performs two ``is None`` checks — no lock, no clock read, no
  allocation growth. The bench obs-overhead smoke asserts the enabled
  path stays within 2% of this.
- **Enabled**: one ``perf_counter`` read on enter, one on exit, and a
  bounded ``deque.append`` under a lock. Never a device sync.

Two things outlive the ring being on. ``phase(name)`` marks the rare,
run-level phases of a run's set-up (``setup/*``): always recorded, into
the ring when it is on and into the flight recorder either way, and
read back with ``phases()``. ``last()`` is the tracer most recently
uninstalled by ``disable()``: a run's timeline after whoever enabled the
ring has closed it.

XLA correlation: the ring's spans are put on a device trace's clock
afterwards, by anchoring the k-th ``dispatch`` span to the k-th module
event (``benchmarks/harness/trace.py::place_host_spans``). Writing them
into the profiler's own trace (``jax.profiler.TraceAnnotation``) was tried
on the v5e and removed: it needs host events on, and at
``host_tracer_level=1`` the feed's layout change still writes 400,000
``Transpose`` events a batch, which starves the feed (PERF.md, PR 25).
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

from . import flight

__all__ = ["SpanTracer", "enable", "disable", "get_tracer", "enabled",
           "last", "span", "step_span", "phase", "record_phase", "phases"]

# module-level pointer: the `is None` check is the entire disabled-path
# cost, so spans stay near-free in un-instrumented processes
_TRACER: Optional["SpanTracer"] = None
# the tracer most recently uninstalled, so a run's spans can be read after
# whoever enabled the ring has disabled it and gone
_LAST: Optional["SpanTracer"] = None


class SpanTracer:
    """Bounded ring of completed host spans, one ring per process.

    Events are recorded with absolute wall-clock microsecond timestamps
    (``ts = epoch + perf_counter delta``) so traces from cooperating
    processes can be merged by a viewer without re-basing.
    """

    def __init__(self, capacity: int = 65536):
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        self.capacity = capacity
        self.dropped = 0          # spans evicted from the ring
        self.recorded = 0
        # perf_counter -> wall-clock anchor, taken once
        self._wall0 = time.time()
        self._perf0 = time.perf_counter()

    # ------------------------------------------------------- recording
    def _abs_us(self, t_perf: float) -> float:
        return (self._wall0 + (t_perf - self._perf0)) * 1e6

    def record(self, name: str, t_start: float, duration: float,
               args: Optional[Dict[str, Any]] = None) -> None:
        """Append one completed span; ``t_start`` is a ``perf_counter``
        value, ``duration`` in seconds."""
        th = threading.current_thread()
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
            self.recorded += 1
            self._ring.append((name, th.ident, th.name,
                               self._abs_us(t_start), duration * 1e6,
                               args))

    # -------------------------------------------------------- snapshot
    def events(self) -> List[Dict[str, Any]]:
        """Chrome trace-event dicts for every retained span, prefixed
        with per-thread name metadata events."""
        with self._lock:
            ring = list(self._ring)
        pid = os.getpid()
        threads = {}
        for _, tid, tname, _, _, _ in ring:
            threads.setdefault(tid, tname)
        out: List[Dict[str, Any]] = [
            {"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
             "args": {"name": tname}}
            for tid, tname in threads.items()]
        for name, tid, _, ts, dur, args in ring:
            ev: Dict[str, Any] = {
                "ph": "X" if dur > 0 else "i", "name": name, "pid": pid,
                "tid": tid, "ts": round(ts, 3)}
            if dur > 0:
                ev["dur"] = round(dur, 3)
            else:
                ev["s"] = "t"          # instant event scope: thread
            if args:
                ev["args"] = args
            out.append(ev)
        return out

    def dump(self, path: str) -> str:
        """Write ``trace.json`` (Chrome trace-event JSON). Loadable by
        Perfetto / chrome://tracing; ``tools/obs_report.py`` renders the
        phase breakdown from the same file; ``tools/trace_merge.py``
        joins per-replica dumps by the identity stamped here."""
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        events = self.events()
        other: Dict[str, Any] = {"recorded": self.recorded,
                                 "dropped": self.dropped}
        run_id = os.environ.get("DLTPU_RUN_ID")
        replica = os.environ.get("DLTPU_REPLICA")
        if run_id:
            other["run_id"] = run_id
        if replica is not None and replica != "":
            other["replica"] = replica
            # name the process row so a merged fleet timeline shows
            # "replica-N" instead of a bare pid
            events.insert(0, {
                "ph": "M", "name": "process_name", "pid": os.getpid(),
                "tid": 0, "args": {"name": f"replica-{replica}"}})
        doc = {"traceEvents": events, "displayTimeUnit": "ms",
               "otherData": other}
        with open(path, "w") as f:
            json.dump(doc, f)
        return path

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.dropped = 0
            self.recorded = 0


# --------------------------------------------------------------- toggles
def enable(capacity: int = 65536) -> SpanTracer:
    """Install (or return) the process-wide tracer. Idempotent: a second
    enable keeps the existing ring so layered callers (Trainer + tests)
    share one timeline."""
    global _TRACER
    if _TRACER is None:
        _TRACER = SpanTracer(capacity=capacity)
    return _TRACER


def disable() -> Optional[SpanTracer]:
    """Uninstall the tracer; returns it (un-dumped spans stay readable,
    also through ``last()``)."""
    global _TRACER, _LAST
    t, _TRACER = _TRACER, None
    if t is not None:
        _LAST = t
    return t


def last() -> Optional[SpanTracer]:
    """The tracer most recently uninstalled by ``disable()``, or None.
    Its ``events()`` are that run's timeline: what to look at after
    ``Trainer.train()`` has returned without a ``workdir``, and what a
    benchmark reader sees once the driver has closed its window. Held
    until the next ``disable()`` replaces it (one bounded ring)."""
    return _LAST


def get_tracer() -> Optional[SpanTracer]:
    return _TRACER


def enabled() -> bool:
    return _TRACER is not None


class span:
    """``with span("data_wait"): ...`` — records one host span.

    Slotted, lock-free and clock-free when tracing is disabled. ``args``
    may be added to inside the block; they are recorded at exit."""

    __slots__ = ("name", "args", "_t0")

    def __init__(self, name: str, **args: Any):
        self.name = name
        self.args = args or None
        self._t0 = None

    def __enter__(self) -> "span":
        if _TRACER is not None:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        tracer = _TRACER
        if tracer is not None and self._t0 is not None:
            tracer.record(self.name, self._t0,
                          time.perf_counter() - self._t0, self.args)
        self._t0 = None
        return False


def step_span(name: str, step_num: int) -> span:
    """Per-training-step span: a ``span`` that carries ``step``."""
    return span(name, step=step_num)


def record_phase(name: str, t0: float, **args: Any) -> float:
    """Record the phase ``name`` that began at the ``perf_counter`` read
    ``t0`` and ends now; returns its seconds. The form for a start taken
    before this module could be imported (the package's own import)."""
    seconds = time.perf_counter() - t0
    tracer = _TRACER
    if tracer is not None:
        tracer.record(name, t0, seconds, args or None)
    flight.record("phase", name=name, seconds=seconds, t0=t0, **args)
    return seconds


@contextlib.contextmanager
def phase(name: str, **args: Any) -> Iterator[None]:
    """``with phase("setup/model_init"): ...`` — a rare, run-level phase
    that is ALWAYS recorded: into the span ring when the ring is on (so
    ``trace.json`` shows it) and, on or off, into the flight recorder as
    ``{"kind": "phase", "name", "seconds", "t0"}`` (``t0``: the
    ``perf_counter`` read at entry), where ``phases()`` finds it. Two
    clock reads, a lock and a dict: for the dozen phases of a run's set-up,
    never inside the step loop (that is what ``span`` is for). The flight
    recorder keeps phase events apart from its ring of 256, so a long run
    with obs on (one ``step`` event per step) does not push the set-up's
    phases out before someone reads them."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        record_phase(name, t0, **args)


def phases() -> List[Dict[str, Any]]:
    """The recorded phase events, oldest first (the recorder keeps them
    where its ring's eviction by other kinds cannot reach)."""
    return flight.get_recorder().events("phase")
