"""Overlapped device feed: a threaded host→HBM prefetch stage.

PR 1 made the *fetch* side of the hot loop sync-free (DeferredMetrics);
this is the *feed*-side counterpart. ``Trainer._train_one_epoch`` used to
pay a blocking ``make_global_array`` host→device transfer on the consumer
thread before every ``train_step`` dispatch — serial feed is the single
biggest non-compute slice of the step on a fast chip. ``DevicePrefetcher``
moves that transfer onto a background thread with a bounded depth-k
queue, so batch k+1's decode **and** H2D copy overlap batch k's compute.

Unlike the bare ``prefetch_to_device`` generator, the prefetcher
preserves the full loader protocol (``__len__``, ``set_epoch``,
``last_data_wait``, ``mesh``) so the Trainer — and anything else written
against ``DataLoader`` — can wrap any loader transparently, including
across epochs. It is also the single place that owns the transfer: when
the wrapped loader is a ``DataLoader`` with a mesh, the prefetcher takes
over its device-put (``loader.device_transfer = False``) so batches are
transferred exactly once, on the worker thread (the double-transfer
``build.py`` used to do is structurally impossible here). With the
transfer it takes over the loader's ``device_transform``: the batch goes
over the wire as the loader made it (uint8 images, a quarter of float32's
bytes) and one jitted call makes it the batch the step was compiled for,
with the put's sharding. That call is dispatched on the CONSUMER's side,
as ``__iter__`` hands the batch over, not by the worker: a second
dispatcher only queues behind the steps the training thread already has
in flight, and on a v5e that wait read as 75-137 ms of ``feed/h2d`` a
batch where the put itself takes under 1 ms (PERF.md, PR 28).

Telemetry (feeds the Trainer's ``data_time`` and per-epoch ``feed/*``
scalars):
- ``last_data_wait`` / ``data_wait_total``: time the CONSUMER actually
  blocked on the queue — true feed starvation, not wall clock.
- ``h2d_wait_total``: worker-thread time spent assembling/transferring
  device arrays (the cost the pipeline hides).
- ``occupancy_mean`` / ``stats()``: queue depth observed at each get —
  near ``depth`` means the feed keeps up, near 0 means input-bound.
  ``stats()`` and ``reset_stats()`` mean "this epoch" (the Trainer resets
  at every epoch end); ``totals()`` never resets.

Timeline (when the span ring is on): the worker numbers its batches and
records ``feed/decode`` (the loader's ``next``: the gather or the
per-sample fetches, and its host ``transform``), ``feed/h2d`` (the put)
and ``feed/put_wait`` (blocked on a full queue) with ``batch=<n>`` — the three tile its loop — and
``last_batch`` is the number of the batch the consumer last received, which
the Trainer puts on its ``data_wait`` span: one identifier from decode to
the step that used the batch.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from typing import Any, Dict, Iterator, Optional

import jax
import numpy as np

from ..obs import spans
from ..obs import threads as obs_threads
from ..parallel.sharding import make_global_array

_END = object()          # producer exhausted its epoch normally
# the per-epoch counters that ``totals()`` carries across ``reset_stats()``
_COUNTERS = ("data_wait_total", "h2d_wait_total", "source_wait_total",
             "batches_fed")


class _WorkerError:
    """Exception carrier: re-raised on the consumer thread."""

    def __init__(self, exc: BaseException):
        self.exc = exc


class DevicePrefetcher:
    """Bounded background-thread device feed wrapping any loader.

    - ``depth``: max batches resident in HBM ahead of the consumer (the
      queue bound; 2 hides one full transfer+decode behind each step
      without hoarding device memory).
    - ``mesh``: assemble numpy leaves into GLOBAL sharded arrays via
      ``make_global_array`` (multi-host correct). Defaults to the wrapped
      loader's own mesh, whose per-batch transfer is taken over.
    - ``sharding``: single-host NamedSharding device_put (mutually
      exclusive with mesh).
    Leaves that are already ``jax.Array`` pass through untouched, so
    wrapping a loader that device-puts internally never double-transfers.
    """

    def __init__(self, loader, depth: int = 2, *,
                 mesh=None, sharding=None, spec=None):
        if mesh is not None and sharding is not None:
            raise ValueError("pass mesh OR sharding, not both")
        self.loader = loader
        self.depth = max(int(depth), 1)
        self.sharding = sharding
        self.spec = spec
        # take over the wrapped loader's transfer so every batch is
        # device-put exactly once, on OUR worker thread (honest
        # h2d_wait_total, and build.py can't double-transfer)
        if mesh is None and sharding is None:
            mesh = getattr(loader, "mesh", None)
        self.mesh = mesh
        # what the loader would run on the batch after its own transfer;
        # __iter__ dispatches it (see the module docstring for why)
        self._device_transform = None
        if self.mesh is not None and \
                getattr(loader, "device_transfer", None) is True and \
                getattr(loader, "mesh", None) is self.mesh:
            loader.device_transfer = False
            self._device_transform = getattr(loader, "device_transform",
                                             None)
        self.epoch = getattr(loader, "epoch", 0)
        # consumer-side starvation telemetry (the DataLoader surface)
        self.last_data_wait: Optional[float] = None
        self.data_wait_total = 0.0
        # worker-side H2D telemetry
        self.h2d_wait_total = 0.0
        self.source_wait_total = 0.0
        self.batches_fed = 0
        self._occ_sum = 0
        self._occ_n = 0
        self._carried = dict.fromkeys(_COUNTERS, 0.0)   # of epochs reset
        # one number per batch for the whole run (across epochs and
        # restarted pipelines), given by the worker, read by the consumer
        self._batch_ids = itertools.count()
        self.last_batch: Optional[int] = None
        self._active: Optional[Dict[str, Any]] = None   # started pipeline

    # ------------------------------------------------- loader protocol
    def __len__(self) -> int:
        return len(self.loader)

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch
        if hasattr(self.loader, "set_epoch"):
            self.loader.set_epoch(epoch)
        # a pipeline started for a different epoch is stale — discard it
        if self._active is not None and self._active["epoch"] != epoch:
            self._shutdown(self._active)
            self._active = None

    def element_spec(self):
        """Delegate abstract batch shapes (AOT warmup) to the loader."""
        fn = getattr(self.loader, "element_spec", None)
        return fn() if fn is not None else None

    def reseed(self, salt: int) -> None:
        """Delegate divergence-recovery reseeding (skip-the-window) to
        the wrapped loader, discarding any already-started pipeline —
        its batches were drawn from the old permutation."""
        fn = getattr(self.loader, "reseed", None)
        if fn is not None:
            fn(salt)
        self._drop_started()

    def _drop_started(self) -> None:
        if self._active is not None:
            self._shutdown(self._active)
            self._active = None

    @property
    def infinite(self) -> bool:
        """The wrapped loader's endless mode (one epoch cycles the set,
        reshuffled every pass). Setting it drops a pipeline that was
        already started, whose batches came from the other mode."""
        return bool(getattr(self.loader, "infinite", False))

    @infinite.setter
    def infinite(self, value: bool) -> None:
        self.loader.infinite = bool(value)
        self._drop_started()

    @property
    def quarantine(self):
        """The wrapped loader's QuarantineLog, if any."""
        return getattr(self.loader, "quarantine", None)

    # ---------------------------------------------------- device place
    def _to_device(self, batch):
        def put(x):
            if isinstance(x, jax.Array):
                return x                      # already placed — no copy
            x = np.asarray(x)  # dltpu: allow(DLT100) H2D staging, worker thread
            if self.mesh is not None:
                return make_global_array(x, self.mesh, self.spec)
            if self.sharding is not None:
                return jax.device_put(x, self.sharding)
            return jax.device_put(x)
        return jax.tree.map(put, batch)

    # -------------------------------------------------------- pipeline
    def _worker(self, it, q: "queue.Queue", stop: threading.Event) -> None:
        try:
            while not stop.is_set():
                t0 = time.perf_counter()
                try:
                    batch = next(it)
                except StopIteration:
                    break
                t1 = time.perf_counter()
                batch = self._to_device(batch)
                t2 = time.perf_counter()
                self.source_wait_total += t1 - t0
                self.h2d_wait_total += t2 - t1
                n = next(self._batch_ids)
                # trace lanes from the worker thread — reuses the clock
                # reads above, so the disabled path costs one None check
                tracer = spans.get_tracer()
                if tracer is not None:
                    tracer.record("feed/decode", t0, t1 - t0, {"batch": n})
                    tracer.record("feed/h2d", t1, t2 - t1, {"batch": n})
                # bounded put that stays responsive to shutdown
                while not stop.is_set():
                    try:
                        q.put((n, batch), timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if tracer is not None:
                    # blocked on a full queue: completes the worker's lane
                    tracer.record("feed/put_wait", t2,
                                  time.perf_counter() - t2, {"batch": n})
            if not stop.is_set():
                q.put(_END)
        except BaseException as exc:  # noqa: BLE001 - relayed to consumer
            # same responsive bounded-put as the data path: a one-shot
            # put(timeout=1.0) against a full queue used to DROP the
            # exception, turning a worker crash into a silent early end
            # of the epoch — the consumer must re-raise it, with the
            # original traceback riding on exc.__traceback__
            while not stop.is_set():
                try:
                    q.put(_WorkerError(exc), timeout=0.1)
                    break
                except queue.Full:
                    continue

    def start(self) -> None:
        """Eagerly start producing the CURRENT epoch's batches.

        Lets the caller overlap first-batch decode+transfer with other
        host work — ``Trainer.precompile()`` runs the AOT step compile
        while this queue fills. ``__iter__`` consumes the started
        pipeline instead of spinning up a second one."""
        if self._active is None:
            self._active = self._start()

    def _start(self) -> Dict[str, Any]:
        q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        stop = threading.Event()
        thread = obs_threads.spawn(
            self._worker, args=(iter(self.loader), q, stop),
            name="device-prefetch", daemon=True)
        return {"queue": q, "stop": stop, "thread": thread,
                "epoch": self.epoch}

    @staticmethod
    def _shutdown(pipe: Dict[str, Any]) -> None:
        pipe["stop"].set()
        try:                      # unblock a producer stuck in put()
            while True:
                pipe["queue"].get_nowait()
        except queue.Empty:
            pass
        pipe["thread"].join(timeout=5.0)

    def __iter__(self) -> Iterator[Any]:
        pipe, self._active = (self._active or self._start()), None
        q = pipe["queue"]
        try:
            while True:
                t0 = time.perf_counter()
                item = q.get()
                self.last_data_wait = time.perf_counter() - t0
                self.data_wait_total += self.last_data_wait
                if item is _END:
                    break
                if isinstance(item, _WorkerError):
                    raise item.exc
                self._occ_sum += q.qsize()
                self._occ_n += 1
                self.batches_fed += 1
                self.last_batch, batch = item
                if self._device_transform is not None:
                    batch = self._device_transform(batch)
                yield batch
        finally:
            self._shutdown(pipe)

    # ------------------------------------------------------- telemetry
    @property
    def occupancy_mean(self) -> float:
        """Mean queue depth seen at each consumer get (0..depth)."""
        return self._occ_sum / self._occ_n if self._occ_n else 0.0

    def stats(self) -> Dict[str, float]:
        """Feed telemetry of this epoch: the Trainer logs it as ``feed/*``
        scalars at every epoch end, then resets it."""
        busy = self.source_wait_total + self.h2d_wait_total
        out = {
            "prefetch_depth": float(self.depth),
            "prefetch_occupancy": self.occupancy_mean,
            "batches_fed": float(self.batches_fed),
            "data_wait_total": self.data_wait_total,
            "h2d_wait_total": self.h2d_wait_total,
            "h2d_wait_frac": (self.h2d_wait_total / busy) if busy else 0.0,
        }
        if self.quarantine is not None:
            out["quarantined"] = float(self.quarantine.quarantined)
        return out

    def totals(self) -> Dict[str, float]:
        """The counters over the whole run: what ``reset_stats()`` has
        wiped at epoch ends plus the epoch in progress."""
        return {k: self._carried[k] + getattr(self, k) for k in _COUNTERS}

    def reset_stats(self) -> None:
        for k in _COUNTERS:
            self._carried[k] += getattr(self, k)
        self.last_data_wait = None
        self.data_wait_total = 0.0
        self.h2d_wait_total = 0.0
        self.source_wait_total = 0.0
        self.batches_fed = 0
        self._occ_sum = 0
        self._occ_n = 0
