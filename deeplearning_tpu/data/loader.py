"""Input pipeline: per-host sharded batching with device prefetch.

Replaces the reference's Dataset/DataLoader/DistributedSampler stack
(SURVEY.md L3; others/train_with_DDP/train.py:140-141, YOLOX
data_prefetcher.py:8 CUDA-stream prefetch). TPU-first shape: every host
loads ONLY its slice of the global batch (the DistributedSampler
successor), batches are fixed-shape (drop_last semantics so jit never
retraces), and ``prefetch_to_device`` overlaps host→HBM transfer with
compute — the DataPrefetcher analog without CUDA streams.

Two routes make a batch. An ``ArraySource`` keeps its arrays in their
storage dtype and hands over a batch in ONE fancy index (``images[idx]``);
a ``MapSource``, a ``num_workers`` pool and a loader with a ``quarantine``
log fetch sample by sample and stack. What a batch still needs once it is
on the device — uint8 images scaled to float32, ``ScaleUint8`` — is the
loader's ``device_transform``: one jitted call a batch, made after the
transfer by whoever hands the batch to the consumer (the loader, or the
``DevicePrefetcher`` that took the transfer over), so a quarter of the
bytes cross the wire and ``element_spec`` still describes the batch the
consumer gets. Each loader
tallies one ``feed`` flight event that says which route its batches took.
"""

from __future__ import annotations

import collections
import itertools
from typing import Any, Callable, Dict, Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..elastic import faults
from ..obs import flight
from ..parallel.sharding import batch_spec, make_global_array
from .quarantine import PoisonedData, QuarantineLog, quarantinable
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class ArraySource:
    """In-memory dataset of parallel arrays (images, labels, ...), kept
    in their storage dtype: an index array gathers a whole batch in one
    call, an int one sample."""

    def __init__(self, **arrays: np.ndarray):
        sizes = {k: len(v) for k, v in arrays.items()}
        if len(set(sizes.values())) != 1:
            raise ValueError(f"Array length mismatch: {sizes}")
        self.arrays = arrays
        self.size = next(iter(sizes.values()))

    def __len__(self) -> int:
        return self.size

    def __getitem__(self, idx) -> Dict[str, np.ndarray]:
        return {k: v[idx] for k, v in self.arrays.items()}


class MapSource:
    """Lazy dataset: indices → sample dict via ``fetch`` (the Dataset
    __getitem__ analog; per-sample decode/augment lives in fetch)."""

    def __init__(self, size: int, fetch: Callable[[int], Dict[str, np.ndarray]]):
        self.size = size
        self.fetch = fetch

    def __len__(self) -> int:
        return self.size

    def __getitem__(self, idx):
        if isinstance(idx, (int, np.integer)):
            return self.fetch(int(idx))
        samples = [self.fetch(int(i)) for i in idx]
        return {k: np.stack([s[k] for s in samples]) for k in samples[0]}


@jax.jit
def _uint8_to_unit(x, r, c):
    # TPU f32 division is not correctly rounded (255 of the 256 values
    # differ from numpy's on a v5e) and XLA turns ``x / 255`` into
    # ``x * (1 / 255)`` (126 differ, CPU and TPU). So: q = x * r, then
    # the remainder x - 255 q = (x - 256 q) + q, exact because both
    # operands of each sum lie within a factor two of each other, then
    # q + rem * r. r and c are operands, not constants: XLA would fold
    # ``(x * r) * 256`` into one product and the CPU contract it with
    # the subtraction, which loses q's own rounding.
    f = x.astype(jnp.float32)
    q = f * r
    rem = (f - q * c) + q
    return q + rem * r


def uint8_to_unit(x) -> jax.Array:
    """uint8 → float32 in [0, 1] on the device ``x`` is on, keeping its
    sharding: bit for bit ``x.astype(np.float32) / np.float32(255)`` as
    numpy computes it, for all 256 values, on the CPU and on a TPU."""
    return _uint8_to_unit(x, np.float32(1.0 / 255.0), np.float32(256.0))


class ScaleUint8:
    """``device_transform`` of a loader whose ``key`` leaf travels as
    uint8: one jitted call a batch makes it the float32 image in [0, 1]
    the step was compiled for."""

    def __init__(self, key: str = "image"):
        self.key = key

    def __call__(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        return {**batch, self.key: uint8_to_unit(batch[self.key])}


_FEED_IDS = itertools.count()    # one ``feed`` flight event per loader


def epoch_indices(size: int, *, shuffle: bool, seed: int, epoch: int,
                  drop_last_to: Optional[int] = None) -> np.ndarray:
    """Deterministic per-epoch permutation — sampler.set_epoch(epoch)
    becomes seeding by (seed, epoch)."""
    idx = np.arange(size)
    if shuffle:
        idx = np.random.default_rng((seed, epoch)).permutation(size)
    if drop_last_to:
        idx = idx[: (size // drop_last_to) * drop_last_to]
    return idx


class DataLoader:
    """Fixed-shape global batches, host-sharded, optionally device-put.

    - ``global_batch`` is the batch across ALL hosts/devices; each host
      materializes only its ``global_batch / process_count`` slice.
    - with a mesh, batches are assembled into global jax.Arrays sharded
      over the data axes (multi-host DP); without, plain numpy dicts.
    - ``transform`` works on the host batch, ``device_transform`` (a
      jitted batch → batch function, ``ScaleUint8`` say) on the batch once
      it is on the device; ``element_spec`` describes what comes out of it.
    """

    def __init__(self, source, global_batch: int, *, shuffle: bool = True,
                 seed: int = 0, mesh: Optional[Mesh] = None,
                 transform: Optional[Callable[[Dict], Dict]] = None,
                 device_transform: Optional[Callable[[Dict], Dict]] = None,
                 infinite: bool = False, num_workers: int = 0,
                 lookahead: int = 4, quarantine=None):
        self.source = source
        self.global_batch = global_batch
        self.shuffle = shuffle
        self.seed = seed
        self.mesh = mesh
        self.transform = transform
        self.device_transform = device_transform
        self._feed_id = next(_FEED_IDS)
        self.infinite = infinite
        self.epoch = 0
        self.num_workers = num_workers
        self.lookahead = max(lookahead, 1)
        self._pool = None
        # bad-sample quarantine (README "Self-healing policy"): a
        # QuarantineLog (or a manifest path to build one) switches fetch
        # to per-sample so a decode failure substitutes + logs instead
        # of killing the epoch; None keeps the fast vectorized path.
        self.quarantine: Optional[QuarantineLog] = (
            QuarantineLog(quarantine) if isinstance(quarantine, str)
            else quarantine)
        self._fetch_counter = itertools.count(1)  # bad_sample fault site
        self._last_good: Optional[Dict[str, Any]] = None
        # divergence rollback support: a reseed(salt) perturbs the
        # shuffle seed so the replayed window draws a different
        # permutation — the "skip past the offending data" half of the
        # Trainer's rollback-and-skip.
        self._seed_salt = 0
        # when False, batches are yielded as HOST numpy dicts even with a
        # mesh — a wrapping DevicePrefetcher flips this to take over the
        # host→HBM transfer on its worker thread (exactly one transfer
        # per batch, off the consumer's critical path)
        self.device_transfer = True
        # starvation telemetry (parallel path only): time the consumer
        # actually blocked waiting for decode futures of the LAST yielded
        # batch, and the running total for the epoch. None on the serial
        # path — consumers (Trainer data_time) fall back to wall-clock.
        self.last_data_wait: Optional[float] = None
        self.data_wait_total = 0.0
        n_proc = jax.process_count()
        if global_batch % n_proc:
            raise ValueError(f"global_batch {global_batch} not divisible by "
                             f"process count {n_proc}")
        self.host_batch = global_batch // n_proc

    def __len__(self) -> int:
        return len(self.source) // self.global_batch

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch

    def reseed(self, salt: int) -> None:
        """Perturb the effective shuffle seed (idempotent per ``salt``).
        After a divergence rollback the Trainer replays from its anchor;
        with the SAME permutation it would march straight back into the
        offending batch — a new salt draws a fresh permutation, which is
        the skip."""
        self._seed_salt = int(salt)

    def _effective_seed(self) -> int:
        return self.seed + self._seed_salt * 1_000_003

    def _local_indices(self, epoch: int) -> Iterator[np.ndarray]:
        idx = epoch_indices(len(self.source), shuffle=self.shuffle,
                            seed=self._effective_seed(), epoch=epoch,
                            drop_last_to=self.global_batch)
        # contiguous host slice of each global batch
        p = jax.process_index()
        for start in range(0, len(idx), self.global_batch):
            gbatch = idx[start:start + self.global_batch]
            yield gbatch[p * self.host_batch:(p + 1) * self.host_batch]

    def _finalize(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        if self.transform:
            batch = self.transform(batch)
        self._tally_feed(batch)
        if self.device_transfer:
            if self.mesh is not None:
                batch = {k: make_global_array(np.asarray(v), self.mesh)
                         for k, v in batch.items()}
            if self.device_transform is not None:
                batch = self.device_transform(batch)
        return batch

    def _tally_feed(self, batch: Dict[str, Any]) -> None:
        """This loader's one ``feed`` flight event, its batches counted
        in place (``calls``): the route they take and what goes over the
        wire, so ``flightrec.json`` says what a run did without a trace."""
        gathered = (isinstance(self.source, ArraySource)
                    and self.quarantine is None and not self.num_workers)
        wire = {k: v if hasattr(v, "nbytes") else np.asarray(v)
                for k, v in batch.items()}
        flight.tally(
            "feed", ("feed", self._feed_id),
            route="array_gather" if gathered else "per_sample",
            scaled_on="device" if self.device_transform is not None
            else "host",
            wire_dtype={k: str(v.dtype) for k, v in wire.items()},
            wire_bytes=sum(v.nbytes for v in wire.values()),
            batch=self.global_batch)

    def element_spec(self) -> Optional[Dict[str, jax.ShapeDtypeStruct]]:
        """Abstract (shape, dtype, sharding) of one yielded batch — the
        AOT-warmup surface: ``Trainer.precompile()`` lowers the jitted
        step against these without materializing any data. Derived from
        ONE source sample pushed through ``transform`` and, abstractly,
        through ``device_transform`` (a uint8 source still specs the
        float32 batch the step gets), so it costs a single decode, not a
        batch."""
        try:
            first = int(next(iter(self._local_indices(self.epoch)))[0])
        except StopIteration:       # fewer samples than one global batch
            return None
        sample = self.source[np.asarray([first])]
        if self.transform:
            sample = self.transform(sample)
        # with a mesh the consumer sees GLOBAL sharded arrays (assembled
        # here or by a wrapping DevicePrefetcher); without, host-local
        # numpy batches of host_batch rows
        sharding = (NamedSharding(self.mesh, batch_spec())
                    if self.mesh is not None else None)
        lead = self.global_batch if self.mesh is not None else \
            self.host_batch

        out = {k: jax.ShapeDtypeStruct((lead, *np.shape(v)[1:]),
                                       np.asarray(v).dtype)
               for k, v in sample.items()}
        if self.device_transform is not None:
            out = jax.eval_shape(self.device_transform, out)
        if sharding is not None:
            out = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                           sharding=sharding)
                   for k, v in out.items()}
        return out

    # ------------------------------------------------ per-sample fetch
    def _fetch_one(self, i: int) -> Dict[str, np.ndarray]:
        """One sample through the fault harness (``bad_sample@step:N``
        counts FETCHES); exceptions propagate to the caller — the
        quarantine decision lives on the consumer thread."""
        ordinal = next(self._fetch_counter)
        if faults.consume("bad_sample", "step", step=ordinal):
            raise faults.InjectedBadSample(
                f"injected bad sample at fetch {ordinal} (index {i})")
        return self.source[int(i)]

    def _quarantine_or_raise(self, i: int, exc: BaseException) -> None:
        """Quarantine a per-sample failure, or re-raise it on the
        consumer thread with its original traceback when it is not a
        sample's fault (interrupts, escalation, OOM)."""
        if self.quarantine is None or not quarantinable(exc):
            raise exc
        self.quarantine.record(int(i), exc, step=self.epoch)

    def _assemble(self, local, samples) -> Dict[str, Any]:
        """Stack per-sample dicts into one fixed-shape batch,
        substituting quarantined slots (None) with good samples so jit
        never sees a short batch. A batch with NO survivors is a hard
        error — there is nothing honest to substitute."""
        good = [s for s in samples if s is not None]
        if good:
            self._last_good = good[-1]
            if self.quarantine is not None:
                self.quarantine.note_ok(len(good))
        elif self._last_good is not None:
            good = [self._last_good]
        else:
            raise PoisonedData(
                f"every sample in batch {list(map(int, local))} failed "
                "with none seen before it — nothing to substitute")
        samples = [s if s is not None else good[j % len(good)]
                   for j, s in enumerate(samples)]
        return {k: np.stack([s[k] for s in samples]) for k in samples[0]}

    def _epoch_iter(self, epoch: int) -> Iterator[Dict[str, Any]]:
        if self.num_workers:
            yield from self._epoch_iter_parallel(epoch)
            return
        for local in self._local_indices(epoch):
            if self.quarantine is None:
                yield self._finalize(self.source[local])
                continue
            samples = []
            for i in local:
                try:
                    samples.append(self._fetch_one(int(i)))
                except BaseException as exc:  # noqa: BLE001
                    self._quarantine_or_raise(int(i), exc)
                    samples.append(None)
            yield self._finalize(self._assemble(local, samples))

    def _epoch_iter_parallel(self, epoch: int) -> Iterator[Dict[str, Any]]:
        """num_workers>0: decode samples on a thread pool (the DataLoader
        num_workers analog — PIL/cv2 JPEG decode releases the GIL), keeping
        ``lookahead`` batches of per-sample futures in flight so decode
        overlaps step compute. Worker exceptions surface HERE, on the
        consumer thread with their original tracebacks (``f.result()``
        re-raises) — quarantinable ones substitute + log, everything
        else kills the epoch loudly, never silently."""
        if self._pool is None:
            import concurrent.futures
            self._pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=self.num_workers)
        pending: collections.deque = collections.deque()
        it = self._local_indices(epoch)
        self.data_wait_total = 0.0
        import time as _time

        def submit(local):
            pending.append((local, [self._pool.submit(self._fetch_one, i)
                                    for i in local]))
        try:
            for local in itertools.islice(it, self.lookahead):
                submit(local)
            while pending:
                local, futs = pending.popleft()
                # queue-empty wait: blocking on not-yet-done futures IS
                # the starvation signal (done futures return instantly),
                # so this isolates decode lag from batch assembly below
                t0 = _time.perf_counter()
                samples = []
                for i, f in zip(local, futs):
                    try:
                        samples.append(f.result())
                    except BaseException as exc:  # noqa: BLE001
                        self._quarantine_or_raise(int(i), exc)
                        samples.append(None)
                self.last_data_wait = _time.perf_counter() - t0
                self.data_wait_total += self.last_data_wait
                yield self._finalize(self._assemble(local, samples))
                for local in itertools.islice(it, 1):
                    submit(local)
        finally:
            for _, futs in pending:
                for f in futs:
                    f.cancel()

    def __iter__(self) -> Iterator[Dict[str, Any]]:
        if not self.infinite:
            yield from self._epoch_iter(self.epoch)
            return
        for epoch in itertools.count(self.epoch):
            yield from self._epoch_iter(epoch)


def prefetch_to_device(iterator: Iterator, size: int = 2,
                       sharding: Optional[NamedSharding] = None,
                       mesh: Optional[Mesh] = None) -> Iterator:
    """Overlap host→device copies with compute (DataPrefetcher analog;
    flax.jax_utils.prefetch_to_device surface, mesh-sharding aware).

    Multi-host correct: with a ``mesh``, numpy leaves are assembled into
    GLOBAL sharded arrays via ``make_global_array`` (a bare per-leaf
    ``jax.device_put`` would build process-local arrays whose shapes
    disagree with the jitted step's global batch spec). Leaves that are
    already ``jax.Array`` pass through untouched, so an upstream loader
    that device-puts internally is never double-transferred.

    Prefer :class:`~deeplearning_tpu.data.device_prefetch.DevicePrefetcher`
    for the Trainer path — it keeps the loader protocol (``set_epoch``,
    ``__len__``) and runs the transfer on a real background thread; this
    generator remains the minimal flax-style surface.
    """
    queue: collections.deque = collections.deque()

    def place(x):
        if isinstance(x, jax.Array):
            return x                       # already on device — no copy
        if mesh is not None:
            return make_global_array(np.asarray(x), mesh)
        if sharding is not None:
            return jax.device_put(x, sharding)
        return jax.device_put(x)

    def put(batch):
        queue.append(jax.tree.map(place, batch))

    it = iter(iterator)
    for b in itertools.islice(it, size):
        put(b)
    while queue:
        yield queue.popleft()
        for b in itertools.islice(it, 1):
            put(b)
