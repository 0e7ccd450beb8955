"""Overlapped device feed: DevicePrefetcher protocol/ordering/bounded
depth/telemetry, multi-host-correct prefetch_to_device, element_spec,
batch-buffer donation, AOT precompile, and the pipelined throughput win."""

import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_tpu.core.registry import MODELS
from deeplearning_tpu.data import ArraySource, DataLoader, DevicePrefetcher
from deeplearning_tpu.data.loader import prefetch_to_device
from deeplearning_tpu.parallel import data_parallel_mesh
from deeplearning_tpu.parallel.sharding import batch_spec
from deeplearning_tpu.train import TrainState, make_eval_step, make_train_step
from deeplearning_tpu.train.classification import make_loss_fn, make_metric_fn
from deeplearning_tpu.train.optim import build_optimizer
from deeplearning_tpu.train.schedules import build_schedule
from deeplearning_tpu.train.trainer import Trainer


def synthetic_cls(n=96, seed=0):
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 4, n).astype(np.int32)
    images = rng.normal(0, 0.1, (n, 16, 16, 1)).astype(np.float32)
    for i, l in enumerate(labels):
        images[i, :, l * 4:(l + 1) * 4, 0] += 2.0
    return images, labels


def make_state(seed=0):
    model = MODELS.build("mnist_fcn", num_classes=4, dtype=jnp.float32)
    params = model.init(jax.random.key(seed),
                        jnp.zeros((1, 16, 16, 1)))["params"]
    tx = build_optimizer(
        "sgd", build_schedule("constant", base_lr=0.1), params=params)
    return TrainState.create(apply_fn=model.apply, params=params, tx=tx)


def make_loader(n=96, batch=32, **kw):
    images, labels = synthetic_cls(n)
    return DataLoader(ArraySource(image=images, label=labels),
                      global_batch=batch, seed=0, **kw)


class CountingLoader:
    """Minimal epoch-protocol loader that counts produced batches; batch
    values encode (epoch, index) so ordering tests are exact."""

    def __init__(self, n=50, delay=0.0, shape=(4, 3)):
        self.n = n
        self.delay = delay
        self.shape = shape
        self.epoch = 0
        self.produced = 0

    def __len__(self):
        return self.n

    def set_epoch(self, epoch):
        self.epoch = epoch

    def __iter__(self):
        for i in range(self.n):
            if self.delay:
                time.sleep(self.delay)
            self.produced += 1
            yield {"x": np.full(self.shape, 1000 * self.epoch + i,
                                np.float32)}


class TestDevicePrefetcher:
    def test_ordering_matches_unwrapped(self):
        ref = [np.asarray(b["image"]) for b in make_loader()]
        pf = DevicePrefetcher(make_loader(), depth=2)
        got = [np.asarray(b["image"]) for b in pf]
        assert len(got) == len(ref) == len(pf) == 3
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(a, b)

    def test_yields_device_arrays(self):
        pf = DevicePrefetcher(make_loader(), depth=2)
        batch = next(iter(pf))
        assert all(isinstance(v, jax.Array) for v in batch.values())

    def test_bounded_depth(self):
        src = CountingLoader(n=50)
        pf = DevicePrefetcher(src, depth=2)
        it = iter(pf)
        next(it)
        time.sleep(0.3)      # producer must stall at the queue bound
        # consumed 1 + depth in queue + 1 in the producer's hand (+1 for
        # a put/fetch race at the moment of sampling)
        assert src.produced <= 1 + pf.depth + 2
        it.close()           # generator finally -> worker shutdown

    def test_consumer_telemetry(self):
        src = CountingLoader(n=6, delay=0.002)
        pf = DevicePrefetcher(src, depth=2)
        n = sum(1 for _ in pf)
        assert n == 6
        assert pf.last_data_wait is not None and pf.last_data_wait >= 0
        assert pf.data_wait_total >= pf.last_data_wait
        stats = pf.stats()
        for key in ("prefetch_depth", "prefetch_occupancy", "batches_fed",
                    "data_wait_total", "h2d_wait_total", "h2d_wait_frac"):
            assert key in stats, key
        assert stats["batches_fed"] == 6
        assert 0.0 <= stats["prefetch_occupancy"] <= pf.depth
        assert 0.0 <= stats["h2d_wait_frac"] <= 1.0
        assert stats["h2d_wait_total"] > 0    # worker timed the device_put
        pf.reset_stats()
        assert pf.batches_fed == 0 and pf.stats()["data_wait_total"] == 0.0

    def test_totals_survive_the_epoch_reset(self):
        pf = DevicePrefetcher(CountingLoader(n=5, delay=0.001), depth=2)
        seen = []
        for epoch in range(2):
            pf.set_epoch(epoch)
            assert sum(1 for _ in pf) == 5
            seen.append(pf.stats())
            before = pf.totals()
            pf.reset_stats()           # what the Trainer does per epoch
            assert pf.totals() == before
        assert pf.stats()["batches_fed"] == 0        # "this epoch" stays
        totals = pf.totals()
        assert totals["batches_fed"] == 10
        for key in ("data_wait_total", "h2d_wait_total"):
            assert totals[key] == pytest.approx(
                seen[0][key] + seen[1][key]) and totals[key] > 0
        assert totals["source_wait_total"] >= 10 * 0.001
        pf.set_epoch(2)
        next(iter(pf))                 # an epoch in progress counts too
        assert pf.totals()["batches_fed"] == 11

    def test_batch_numbers_join_the_worker_lane_to_the_consumer(self):
        from deeplearning_tpu.obs import spans
        spans.disable()
        tracer = spans.enable()
        try:
            pf = DevicePrefetcher(CountingLoader(n=4), depth=2)
            received = []
            for epoch in range(2):     # numbers run on across epochs
                pf.set_epoch(epoch)
                for _ in pf:
                    received.append(pf.last_batch)
        finally:
            spans.disable()
        assert received == list(range(8))
        lanes = {}
        for e in tracer.events():
            if e["ph"] == "X" and e["name"].startswith("feed/"):
                assert e["dur"] > 0
                lanes.setdefault(e["name"], []).append(e["args"]["batch"])
        assert lanes == {name: list(range(8)) for name in
                         ("feed/decode", "feed/h2d", "feed/put_wait")}
        # decode, h2d and put_wait of one batch tile the worker's loop
        by_batch = {}
        for e in tracer.events():
            if e["ph"] == "X" and e["name"].startswith("feed/"):
                by_batch.setdefault(e["args"]["batch"], {})[e["name"]] = e
        for lane in by_batch.values():
            dec, h2d, put = (lane[n] for n in
                             ("feed/decode", "feed/h2d", "feed/put_wait"))
            assert dec["ts"] + dec["dur"] == pytest.approx(h2d["ts"], abs=1)
            assert h2d["ts"] + h2d["dur"] == pytest.approx(put["ts"], abs=1)

    def test_infinite_property_drops_a_started_pipeline(self):
        loader = make_loader(n=96, batch=32)
        pf = DevicePrefetcher(loader, depth=2)
        assert pf.infinite is False
        pf.start()                     # the finite pipeline precompile starts
        stale = pf._active
        pf.infinite = True
        assert loader.infinite is True and pf.infinite is True
        assert pf._active is None and stale["stop"].is_set()
        it = iter(pf)
        assert sum(1 for _ in zip(range(7), it)) == 7   # past one 3-batch pass
        it.close()

    def test_epoch_protocol_delegates_and_reshuffles(self):
        ref = make_loader(shuffle=True)
        ref.set_epoch(3)
        want = [np.asarray(b["image"]) for b in ref]
        pf = DevicePrefetcher(make_loader(shuffle=True), depth=2)
        pf.set_epoch(3)
        assert pf.loader.epoch == 3
        got = [np.asarray(b["image"]) for b in pf]
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b)

    def test_started_pipeline_discarded_on_epoch_change(self):
        pf = DevicePrefetcher(CountingLoader(n=4), depth=2)
        pf.start()             # eagerly producing epoch 0
        time.sleep(0.05)
        pf.set_epoch(1)        # stale pipeline must be thrown away
        vals = [float(np.asarray(b["x"]).ravel()[0]) for b in pf]
        assert vals == [1000.0, 1001.0, 1002.0, 1003.0]

    def test_start_then_iter_consumes_same_pipeline(self):
        src = CountingLoader(n=4)
        pf = DevicePrefetcher(src, depth=2)
        pf.start()
        time.sleep(0.1)        # queue fills while "compiling"
        assert src.produced > 0
        vals = [float(np.asarray(b["x"]).ravel()[0]) for b in pf]
        assert vals == [0.0, 1.0, 2.0, 3.0]
        assert src.produced == 4    # one pipeline, not two

    def test_worker_exception_reraised_on_consumer(self):
        class Exploding(CountingLoader):
            def __iter__(self):
                yield {"x": np.zeros((2,), np.float32)}
                raise RuntimeError("decode boom")

        pf = DevicePrefetcher(Exploding(), depth=2)
        with pytest.raises(RuntimeError, match="decode boom"):
            list(pf)

    def test_mesh_and_sharding_mutually_exclusive(self):
        from deeplearning_tpu.parallel.sharding import batch_sharding
        mesh = data_parallel_mesh()
        with pytest.raises(ValueError, match="mesh OR sharding"):
            DevicePrefetcher(CountingLoader(), mesh=mesh,
                             sharding=batch_sharding(mesh))

    def test_mesh_loader_transfer_taken_over(self):
        """Wrapping a mesh DataLoader: the prefetcher adopts the mesh,
        flips device_transfer, and yields GLOBAL sharded arrays assembled
        exactly once (on the worker thread)."""
        loader = make_loader(mesh=data_parallel_mesh())
        assert loader.device_transfer is True
        pf = DevicePrefetcher(loader, depth=2)
        assert pf.mesh is loader.mesh
        assert loader.device_transfer is False
        batches = list(pf)
        assert len(batches) == 3
        for b in batches:
            for v in b.values():
                assert isinstance(v, jax.Array)
                assert v.shape[0] == 32            # global batch dim
                assert v.sharding.mesh.shape == loader.mesh.shape
                assert v.sharding.spec == batch_spec()
        # values survive the thread + shard assembly intact
        ref = make_loader()                        # meshless twin, epoch 0
        for got, want in zip(batches, ref):
            np.testing.assert_array_equal(np.asarray(got["image"]),
                                          want["image"])


class TestPrefetchToDevice:
    def test_mesh_assembles_global_arrays(self):
        mesh = data_parallel_mesh()
        batches = [{"x": np.full((16, 4), i, np.float32)} for i in range(3)]
        out = list(prefetch_to_device(iter(batches), size=2, mesh=mesh))
        assert len(out) == 3
        for i, b in enumerate(out):
            assert isinstance(b["x"], jax.Array)
            assert b["x"].sharding.spec == batch_spec()
            np.testing.assert_array_equal(np.asarray(b["x"]),
                                          np.full((16, 4), i, np.float32))

    def test_device_arrays_pass_through_untouched(self):
        placed = {"x": jnp.ones((8, 2))}
        out = next(prefetch_to_device(iter([placed]), size=1,
                                      mesh=data_parallel_mesh()))
        assert out["x"] is placed["x"]             # no second transfer


class TestElementSpec:
    def test_meshless_spec_is_host_batch(self):
        spec = make_loader(batch=32).element_spec()
        assert set(spec) == {"image", "label"}
        assert spec["image"].shape == (32, 16, 16, 1)
        assert spec["image"].dtype == np.float32
        assert spec["label"].shape == (32,)
        assert spec["image"].sharding is None

    def test_mesh_spec_is_global_and_sharded(self):
        mesh = data_parallel_mesh()
        spec = make_loader(batch=32, mesh=mesh).element_spec()
        assert spec["image"].shape == (32, 16, 16, 1)
        assert spec["image"].sharding.mesh.shape == mesh.shape
        assert spec["image"].sharding.spec == batch_spec()

    def test_too_small_dataset_returns_none(self):
        assert make_loader(n=8, batch=32).element_spec() is None

    def test_prefetcher_delegates(self):
        loader = make_loader(batch=32)
        pf = DevicePrefetcher(loader, depth=2)
        assert pf.element_spec() == loader.element_spec()
        assert DevicePrefetcher(CountingLoader(), depth=1) \
            .element_spec() is None


class TestBatchDonation:
    def test_donate_batch_train_then_eval(self):
        """donate_batch=True over fresh loader batches, then eval: no
        donated-buffer reuse anywhere in the normal Trainer data flow."""
        state = make_state()
        step = make_train_step(make_loss_fn(), donate=True,
                               donate_batch=True)
        eval_step = make_eval_step(make_metric_fn(ks=(1,)))
        loader = make_loader()
        with warnings.catch_warnings():
            # CPU aliases few/no batch buffers -> benign "donated buffers
            # were not usable" warning
            warnings.simplefilter("ignore")
            for batch in loader:
                state, m = step(state, batch, jax.random.key(0))
            counts = eval_step(state, next(iter(loader)))
        assert np.isfinite(float(m["loss"]))
        assert float(counts["count"]) == 32

    def test_opt_out_allows_batch_reuse(self):
        state = make_state()
        step = make_train_step(make_loss_fn(), donate=False,
                               donate_batch=False)
        batch = jax.device_put(next(iter(make_loader())))
        state, m1 = step(state, batch, jax.random.key(0))
        state, m2 = step(state, batch, jax.random.key(1))  # same buffers
        assert np.isfinite(float(m2["loss"]))


class TestPrecompile:
    def test_aot_compile_then_train(self):
        trainer = Trainer(
            state=make_state(),
            train_step=make_train_step(make_loss_fn(), donate=False),
            train_loader=make_loader(),
            epochs=1, log_every=100)
        dt = trainer.precompile()
        assert dt is not None and dt > 0
        assert trainer.precompile_seconds == dt
        assert trainer._aot_step is not None
        assert "HloModule" in trainer.compiled_step_text()
        trainer.train()                      # reuses the AOT executable
        assert trainer.deferred.pending == 0

    def test_no_element_spec_is_noop(self):
        trainer = Trainer(
            state=make_state(),
            train_step=make_train_step(make_loss_fn(), donate=False),
            train_loader=CountingLoader(), prefetch=0,
            epochs=1, log_every=100)
        assert trainer.precompile() is None

    def test_overlaps_prefetcher_start(self):
        src = CountingLoader(n=4, shape=(1, 16, 16, 1))
        pf = DevicePrefetcher(src, depth=2)
        trainer = Trainer(
            state=make_state(),
            train_step=make_train_step(make_loss_fn(), donate=False),
            train_loader=pf, epochs=1, log_every=100)
        assert trainer.precompile() is None  # no spec, but feed started
        time.sleep(0.1)
        assert src.produced > 0              # worker ran during "compile"
        pf.reseed(0)     # stops the started pipeline: its worker would
        #                  outlive the test, parked on the full queue


class SlowSyntheticLoader:
    """Synthetic slow source: each batch costs `delay` s of host work
    (the decode/augment stand-in for the acceptance measurement)."""

    def __init__(self, n=8, batch=32, dim=256, delay=0.008):
        self.n, self.batch, self.dim, self.delay = n, batch, dim, delay
        self.epoch = 0
        self.last_data_wait = None

    def __len__(self):
        return self.n

    def set_epoch(self, epoch):
        self.epoch = epoch

    def __iter__(self):
        rng = np.random.default_rng(self.epoch)
        for _ in range(self.n):
            time.sleep(self.delay)
            yield {"x": rng.normal(size=(self.batch, self.dim))
                   .astype(np.float32)}


@jax.jit
def _heavy_step(state, batch, rng):
    x = batch["x"]
    w = jnp.eye(x.shape[1], dtype=x.dtype) * 0.5

    def body(_, v):
        return jnp.tanh(v @ w)
    y = jax.lax.fori_loop(0, 200, body, x)
    return state, {"loss": jnp.mean(y)}


def _blocking_step(state, batch, rng):
    # models the device-queue-saturated regime (real accelerator feeds
    # block the host in transfer/dispatch once the pipe is full): the
    # host cannot run ahead, so feed/compute overlap must come from the
    # prefetcher's worker thread, not from async dispatch slack
    state, m = _heavy_step(state, batch, rng)
    jax.block_until_ready(m)
    return state, m


class TestPipelinedThroughput:
    """The ISSUE acceptance criterion: DevicePrefetcher(depth=2) over a
    slow synthetic source beats the unwrapped loader on images/sec,
    through ``Trainer.train()``: the one loop."""

    STEPS = 15

    @classmethod
    def _run(cls, prefetch):
        """One epoch of ``STEPS`` + 1 steps; the first (it compiles, and
        the prefetcher's queue is cold) is left out of the clock. Returns
        images/sec over the rest, the share of that time the training
        thread waited for data (the loader's own ``last_data_wait`` where
        it has one, the wall clock otherwise: the Trainer's ``data_time``
        rule), and the loader's ``stats()`` at the last step (the Trainer
        resets them at the epoch's end)."""
        loader = SlowSyntheticLoader(n=cls.STEPS + 1)
        trainer = Trainer(state=None, train_step=_blocking_step,
                          train_loader=loader, epochs=1,
                          retrace_warn=False, prefetch=prefetch,
                          log_every=50)
        marks, waits, stats = [], [], {}

        def before_iter(t, batch):
            if marks:
                wait = getattr(t.train_loader, "last_data_wait", None)
                waits.append(wait if wait is not None
                             else time.perf_counter() - marks[-1])

        def after_iter(t, metrics):
            marks.append(time.perf_counter())
            feed_stats = getattr(t.train_loader, "stats", None)
            if feed_stats is not None:
                stats.update(feed_stats())

        trainer.callbacks.register("before_iter", before_iter)
        trainer.callbacks.register("after_iter", after_iter)
        trainer.train()
        assert len(marks) == cls.STEPS + 1
        total = marks[-1] - marks[0]
        ips = loader.batch * cls.STEPS / total
        return ips, sum(waits) / total, stats

    def test_wrapped_beats_unwrapped(self):
        serial_ips, serial_wait, serial_stats = self._run(prefetch=0)
        piped_ips, piped_wait, piped_stats = self._run(prefetch=2)
        # feed (8 ms) overlaps compute (~8 ms): ~1.4-1.9x in practice;
        # assert a conservative margin so CI load can't flake it
        assert piped_ips > serial_ips * 1.15, \
            f"pipelined {piped_ips:.0f} vs serial {serial_ips:.0f} img/s"
        # wrapped stats carry the feed telemetry, serial ones don't
        assert "prefetch_occupancy" in piped_stats
        assert piped_stats["prefetch_depth"] == 2.0
        assert piped_stats["batches_fed"] == self.STEPS + 1
        assert "prefetch_occupancy" not in serial_stats
        # overlap shows up as less consumer starvation per wall second
        assert piped_wait < serial_wait

    def test_auto_wrap_requires_mesh(self):
        meshless = Trainer(state=None, train_step=_blocking_step,
                           train_loader=SlowSyntheticLoader(),
                           retrace_warn=False, log_every=50)
        assert not isinstance(meshless.train_loader, DevicePrefetcher)
        meshed = Trainer(
            state=make_state(),
            train_step=make_train_step(make_loss_fn(), donate=False),
            train_loader=make_loader(mesh=data_parallel_mesh()),
            epochs=1, log_every=100)
        assert isinstance(meshed.train_loader, DevicePrefetcher)
        assert meshed.train_loader.depth == 2

    def test_explicit_wrap_passthrough(self):
        pf = DevicePrefetcher(SlowSyntheticLoader(), depth=3)
        trainer = Trainer(state=None, train_step=_blocking_step,
                          train_loader=pf, retrace_warn=False,
                          log_every=50)
        assert trainer.train_loader is pf
