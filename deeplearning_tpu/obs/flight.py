"""Crash flight recorder: last-K structured events + config, dumped on
divergence abort, uncaught trainer exception, or SIGTERM.

A diverged or preempted run previously left nothing to autopsy — the
metrics ring dies with the process and the log file stops mid-line. The
recorder keeps a bounded in-memory ring of recent structured events
(step metric snapshots, feed stats, retrace warnings, compile events,
serve rejections — anything a layer ``record()``s) and serializes it to
``runs/<dir>/flightrec.json`` together with the run config, an HBM
snapshot, and the exception, the moment something goes wrong.

Recording is always-on and cheap (bounded ``deque.append`` under a
lock; no device syncs, no I/O); DUMPING requires a path — either
``configure(path, config)`` (the Trainer does this per run) or an
explicit ``dump(path=...)``. The default process-wide recorder is what
the convenience ``record(kind, **data)`` feeds, so layers don't need a
handle threaded through them.
"""

from __future__ import annotations

import collections
import json
import os
import signal
import threading
import time
import traceback
from typing import Any, Dict, List, Optional

__all__ = ["FlightRecorder", "get_recorder", "record", "tally",
           "configure", "dump", "install_signal_handler", "flush_pending",
           "watch_pending"]


def _jsonable(obj: Any, depth: int = 0) -> Any:
    """Best-effort JSON projection: configs arrive as dataclass-dicts,
    numpy scalars, device arrays — serialize what we can, stringify the
    rest (a flight record must never fail to write)."""
    if depth > 6:
        return repr(obj)
    if obj is None or isinstance(obj, (bool, int, str)):
        return obj
    if isinstance(obj, float):
        return obj if obj == obj and abs(obj) != float("inf") else repr(obj)
    if isinstance(obj, dict):
        return {str(k): _jsonable(v, depth + 1) for k, v in obj.items()}
    if isinstance(obj, (list, tuple, set)):
        return [_jsonable(v, depth + 1) for v in obj]
    if hasattr(obj, "item"):           # numpy / jax scalars
        try:
            return _jsonable(obj.item(), depth + 1)
        except Exception:  # noqa: BLE001
            pass
    if hasattr(obj, "__dataclass_fields__"):
        import dataclasses
        try:
            return _jsonable(dataclasses.asdict(obj), depth + 1)
        except Exception:  # noqa: BLE001
            pass
    return repr(obj)


class FlightRecorder:
    """Bounded ring of recent events with a one-shot crash dump."""

    def __init__(self, capacity: int = 256):
        self.capacity = int(capacity)
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        self.path: Optional[str] = None
        self.config: Optional[Dict[str, Any]] = None
        self.dumps = 0
        self.recorded = 0
        self._tallies: Dict[Any, Dict[str, Any]] = {}
        # the run-level ``phase`` events (``obs.spans.phase``: a dozen a
        # run) also go where a step event a step cannot evict them
        self._phases: collections.deque = collections.deque(maxlen=capacity)

    # ------------------------------------------------------- recording
    def record(self, kind: str, **data: Any) -> None:
        event = {"kind": kind, "time": time.time(),
                 "thread": threading.current_thread().name, **data}
        with self._lock:
            self.recorded += 1
            self._ring.append(event)
            if kind == "phase":
                self._phases.append(event)

    def tally(self, kind: str, key: Any, member: Optional[str] = None,
              **data: Any) -> None:
        """One event per ``key``, however often it happens: the first call
        records it, every call bumps its ``calls`` in place and adds
        ``member`` to its ``members`` (who it happened to, each once). For
        what repeats per layer and per trace — which kernel a layer chose,
        at what shape — without costing the ring an event each time."""
        with self._lock:
            event = self._tallies.get(key)
            if event is None or not any(e is event for e in self._ring):
                event = {"kind": kind, "time": time.time(),
                         "thread": threading.current_thread().name, **data,
                         "calls": 0, "members": []}
                self._tallies[key] = event
                self.recorded += 1
                self._ring.append(event)
            event["calls"] += 1
            if member is not None and member not in event["members"]:
                event["members"].append(member)

    def events(self, kind: Optional[str] = None) -> List[Dict[str, Any]]:
        """The ring's events, oldest first, or those of one ``kind``;
        ``"phase"`` reads the phases kept apart, every one of the run."""
        with self._lock:
            if kind == "phase":
                return list(self._phases)
            ring = list(self._ring)
        return ring if kind is None else [e for e in ring
                                          if e["kind"] == kind]

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._tallies.clear()
            self._phases.clear()
            self.recorded = 0

    # --------------------------------------------------------- dumping
    def configure(self, path: str,
                  config: Optional[Any] = None) -> "FlightRecorder":
        """Arm the recorder: where to dump and what run config to embed
        (any object; serialized best-effort)."""
        self.path = path
        self.config = _jsonable(config) if config is not None else None
        return self

    def dump(self, reason: str = "manual", *,
             exception: Optional[BaseException] = None,
             path: Optional[str] = None,
             include_hbm: bool = True) -> Optional[str]:
        """Write ``flightrec.json``; returns the path (None when no path
        is configured — recording without arming is legal). Never raises:
        this runs inside except blocks and signal handlers.

        ``include_hbm=False`` skips the device-memory snapshot — the run
        supervisor uses it because ``hbm_snapshot`` initializes the jax
        backend, and a supervisor must not wedge in the same device init
        it polices."""
        try:
            path = path or self.path
            if not path:
                return None
            exc_info = None
            if exception is not None:
                exc_info = {
                    "type": type(exception).__name__,
                    "message": str(exception),
                    "traceback": traceback.format_exception(
                        type(exception), exception,
                        exception.__traceback__),
                }
            hbm = None
            if include_hbm:
                from .xla import hbm_snapshot   # lazy: avoid import cycle
                hbm = _jsonable(hbm_snapshot())
            doc = {
                "reason": reason,
                "time": time.time(),
                "pid": os.getpid(),
                "config": self.config,
                "exception": exc_info,
                "hbm": hbm,
                "events": _jsonable(self.events()),
            }
            os.makedirs(os.path.dirname(os.path.abspath(path)),
                        exist_ok=True)
            # tmp + replace: a SIGKILL mid-write leaves the last whole dump
            tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1)
            os.replace(tmp, path)
            self.dumps += 1
            return path
        except Exception:  # noqa: BLE001 - a dump failure must not mask
            return None    # the original crash


# process-wide default recorder: layers record into it without plumbing
_RECORDER = FlightRecorder()
_SIGNAL_INSTALLED = False


def get_recorder() -> FlightRecorder:
    return _RECORDER


def record(kind: str, **data: Any) -> None:
    """Append one event to the default recorder (always cheap/bounded)."""
    _RECORDER.record(kind, **data)


def tally(kind: str, key: Any, member: Optional[str] = None,
          **data: Any) -> None:
    """One event per ``key`` in the default recorder, counted in place."""
    _RECORDER.tally(kind, key, member, **data)


def configure(path: str, config: Optional[Any] = None) -> FlightRecorder:
    return _RECORDER.configure(path, config)


def dump(reason: str = "manual", *,
         exception: Optional[BaseException] = None,
         path: Optional[str] = None) -> Optional[str]:
    return _RECORDER.dump(reason, exception=exception, path=path)


_PENDING = threading.Event()
_PENDING_LOCK = threading.RLock()      # re-entered by the handler's chain


def _sigterm_dump(signum: int, frame) -> None:
    # Signal-handler discipline (DLT103): mark the dump pending and get
    # out. When a graceful subscriber owns this signal the process
    # keeps running, and flush_pending() does the open()/json work on a
    # normal call stack: the Trainer's next step boundary, or the
    # ``watch_pending`` thread when the main thread never gets there.
    _PENDING.set()
    from ..elastic import signals
    if any(graceful for _fn, graceful
           in signals.subscribers(signal.SIGTERM)):
        return
    # Terminating chain: no graceful owner means the pre-registry
    # handler / OS default kills the process right after this handler
    # returns — there IS no later flush point, so the unsafe dump here
    # is the only dump. Justified, not fixed:
    flush_pending()  # dltpu: allow(DLT103) terminating chain: last chance to write


def flush_pending() -> Optional[str]:
    """Write a dump the SIGTERM handler deferred; no-op when none is
    pending. Called from the Trainer's step boundary (next to the
    preemption poll), its graceful-exit path and the ``watch_pending``
    thread; whichever comes first writes it, and the others return once it
    is written (so a later dump, such as the preemption's, lands after)."""
    with _PENDING_LOCK:
        if not _PENDING.is_set():
            return None
        _PENDING.clear()
        return _RECORDER.dump("sigterm")


def watch_pending():
    """Start a thread that writes a deferred SIGTERM dump as soon as the
    handler marks it: a wedged main thread (a device call that never
    returns) reaches no step boundary, and the supervisor's SIGKILL
    follows its grace. Returns the function that stops the thread."""
    from . import threads as obs_threads
    stop = threading.Event()

    def run() -> None:
        while not stop.is_set():
            if _PENDING.wait(0.05):
                flush_pending()

    thread = obs_threads.spawn(run, name="flight-pending", daemon=True)

    def halt() -> None:
        stop.set()
        thread.join(2.0)
    return halt


def install_signal_handler() -> bool:
    """Dump on SIGTERM (preemption / driver kill). Subscribes through
    the elastic signal registry, so this hook COEXISTS with the
    preemption guard instead of silently replacing it: without a
    graceful subscriber the process still terminates after the dump
    (pre-registry handler or OS default chained); with one, the
    handler only marks the dump pending and the trainer flushes it at
    the next step boundary (``flush_pending``) before checkpointing
    out. Main thread only; returns False when it isn't."""
    global _SIGNAL_INSTALLED
    if _SIGNAL_INSTALLED:
        return True
    from ..elastic import signals      # lazy: flight must import light
    if signals.subscribe(signal.SIGTERM, _sigterm_dump):
        _SIGNAL_INSTALLED = True
        return True
    return False
