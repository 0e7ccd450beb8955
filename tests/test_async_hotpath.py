"""Sync-free hot path: DeferredMetrics staleness, device-side divergence
guard, zero-sync eval, retrace guard, and the persistent compile cache."""

import os
import time
import warnings
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_tpu.core.registry import MODELS
from deeplearning_tpu.data import ArraySource, DataLoader
from deeplearning_tpu.train import TrainState, make_eval_step, make_train_step
from deeplearning_tpu.train.async_metrics import DeferredMetrics
from deeplearning_tpu.train.classification import make_loss_fn, make_metric_fn
from deeplearning_tpu.train.optim import build_optimizer
from deeplearning_tpu.train.schedules import build_schedule
from deeplearning_tpu.train.trainer import Trainer
from deeplearning_tpu.utils.profiling import RetraceGuard


class TestDeferredMetrics:
    def test_staleness_and_ordering(self):
        ring = DeferredMetrics(lag=3)
        for i in range(10):
            ring.push({"loss": jnp.asarray(float(i))}, it=i)
        # entries with >= 3 newer entries behind them are ready: 0..6
        ready = ring.poll()
        assert [m["it"] for m, _ in ready] == list(range(7))
        assert [h["loss"] for _, h in ready] == [float(i) for i in range(7)]
        assert ring.pending == 3
        assert ring.fetch_count == 1          # one sync event for 7 entries
        assert ring.fetched_entries == 7
        # nothing new became ready -> no extra sync event
        assert ring.poll() == []
        assert ring.fetch_count == 1
        rest = ring.drain()
        assert [m["it"] for m, _ in rest] == [7, 8, 9]
        assert ring.fetch_count == 2 and ring.pending == 0

    def test_zero_lag_materializes_immediately(self):
        ring = DeferredMetrics(lag=0)
        ring.push({"x": jnp.asarray(1.0)})
        ready = ring.poll()
        assert len(ready) == 1 and ready[0][1]["x"] == 1.0

    def test_meta_is_passed_through_host_side(self):
        ring = DeferredMetrics(lag=0)
        ring.push({"x": jnp.asarray(2.0)}, epoch=3, data_time=0.5)
        (meta, host), = ring.poll()
        assert meta["epoch"] == 3 and meta["data_time"] == 0.5


class TestWindowedMetrics:
    def test_window_means_and_meta(self):
        ring = DeferredMetrics(lag=0, window=4)
        for i in range(8):
            ring.push({"loss": jnp.asarray(float(i))}, it=i)
        ready = ring.poll()
        assert [h["loss"] for _, h in ready] == [1.5, 5.5]   # window means
        assert [m["it"] for m, _ in ready] == [3, 7]   # last step's meta
        assert ring.fetch_count == 1 and ring.fetched_entries == 2

    def test_host_state_is_o1_per_step(self):
        """100 pushes at window=10 hold 10 closed windows + one device
        accumulator — never 100 per-step dicts."""
        ring = DeferredMetrics(lag=0, window=10)
        for i in range(105):
            ring.push({"loss": jnp.asarray(1.0)}, it=i)
        assert len(ring._buf) == 10
        assert ring._open_n == 5
        assert ring.pending == 11

    def test_bad_step_is_summed_not_averaged(self):
        ring = DeferredMetrics(lag=0, window=4)
        for i in range(4):
            ring.push({"loss": jnp.asarray(1.0),
                       "bad_step": jnp.int32(1 if i == 2 else 0)})
        (_, host), = ring.poll()
        assert host["bad_step"] == 1.0        # any bad step survives
        assert host["loss"] == 1.0

    def test_nan_poisons_window_mean(self):
        ring = DeferredMetrics(lag=0, window=3)
        for v in (1.0, float("nan"), 2.0):
            ring.push({"loss": jnp.asarray(v)})
        (_, host), = ring.poll()
        assert not np.isfinite(host["loss"])

    def test_lag_counts_pushes_since_close(self):
        ring = DeferredMetrics(lag=3, window=2)
        for i in range(4):                    # windows close at push 2, 4
            ring.push({"x": jnp.asarray(float(i))})
        assert ring.poll() == []              # newest close only 0 old
        for i in range(2):                    # pushes 5, 6
            ring.push({"x": jnp.asarray(float(i))})
        ready = ring.poll()                   # first window now 4 old
        assert len(ready) == 1 and ready[0][1]["x"] == 0.5
        assert ring.pending == 2              # windows closed at 4 and 6

    def test_drain_closes_partial_window(self):
        ring = DeferredMetrics(lag=5, window=4)
        for i in range(3):
            ring.push({"x": jnp.asarray(float(i))})
        entries = ring.drain()
        assert len(entries) == 1 and entries[0][1]["x"] == 1.0
        assert ring.pending == 0

    def test_trainer_auto_window_and_divergence(self):
        """log_every > 100 turns the windowed reduction on; the NaN
        abort still fires through the window-mean path."""
        trainer = make_trainer(epochs=1, log_every=150, n=5 * 16, batch=16)
        assert trainer.metrics_window == 150
        trainer.train()
        # 5 steps fold into ONE partial window drained at epoch end
        assert trainer.deferred.fetched_entries == 1
        assert trainer.deferred.fetch_count <= 1

        base = make_train_step(make_loss_fn(), donate=False)

        def nan_step(state, batch, rng):
            state, metrics = base(state, batch, rng)
            bad = jnp.float32(float("nan"))
            return state, {**metrics, "loss": bad, "bad_step": jnp.int32(1)}

        trainer = make_trainer(nan_step, epochs=1, log_every=150,
                               n=5 * 16, batch=16)
        with pytest.raises(FloatingPointError, match="non-finite"):
            trainer.train()


def synthetic_cls(n=96, seed=0):
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 4, n).astype(np.int32)
    images = rng.normal(0, 0.1, (n, 16, 16, 1)).astype(np.float32)
    for i, l in enumerate(labels):
        images[i, :, l * 4:(l + 1) * 4, 0] += 2.0
    return images, labels


def make_trainer(train_step=None, *, epochs=1, log_every=100, n=96,
                 metrics_lag=None, batch=32, **trainer_kw):
    images, labels = synthetic_cls(n)
    model = MODELS.build("mnist_fcn", num_classes=4, dtype=jnp.float32)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 16, 16, 1)))["params"]
    tx = build_optimizer(
        "sgd", build_schedule("constant", base_lr=0.1), params=params)
    state = TrainState.create(apply_fn=model.apply, params=params, tx=tx)
    loader = DataLoader(ArraySource(image=images, label=labels),
                        global_batch=batch, seed=0)
    eval_loader = DataLoader(ArraySource(image=images, label=labels),
                             global_batch=batch, shuffle=False)
    return Trainer(
        state=state,
        train_step=train_step or make_train_step(make_loss_fn(),
                                                 donate=False),
        train_loader=loader,
        eval_step=make_eval_step(make_metric_fn(ks=(1,))),
        eval_loader=eval_loader,
        epochs=epochs, log_every=log_every, metrics_lag=metrics_lag,
        **trainer_kw)


class TestZeroSyncHotLoop:
    def test_smoke_five_steps_at_most_one_sync(self):
        """5 Trainer steps with the async pipeline: the mid-epoch polls
        find nothing ready (lag = log_every > 5) and the epoch-end drain
        is the single bulk fetch -> exactly one metrics sync event."""
        trainer = make_trainer(epochs=1, log_every=100, n=5 * 16, batch=16)
        assert len(trainer.train_loader) == 5
        trainer.train()
        assert trainer.deferred.fetched_entries == 5   # every step checked
        assert trainer.deferred.fetch_count <= 1
        assert trainer.deferred.pending == 0

    def test_wrapped_loader_keeps_sync_bound(self):
        """Same ≤1-sync bound with the hot loop fed through a
        DevicePrefetcher: the overlapped feed must not reintroduce any
        D2H fetch between log points."""
        from deeplearning_tpu.data import DevicePrefetcher
        trainer = make_trainer(epochs=1, log_every=100, n=5 * 16, batch=16,
                               prefetch=2)
        assert isinstance(trainer.train_loader, DevicePrefetcher)
        assert len(trainer.train_loader) == 5
        trainer.train()
        assert trainer.deferred.fetched_entries == 5
        assert trainer.deferred.fetch_count <= 1
        assert trainer.deferred.pending == 0
        # feed telemetry flowed through the epoch-end reset
        assert trainer.train_loader.batches_fed == 0   # reset after epoch

    def test_device_side_guard_aborts_within_lag_window(self):
        """Injected NaN loss at step N aborts within metrics_lag +
        log_every steps (K*log_every with K=2 at the default lag), via
        the jitted bad_step flag on the stale snapshot."""
        base = make_train_step(make_loss_fn(), donate=False)
        calls = {"n": 0}

        def nan_after_3(state, batch, rng):
            calls["n"] += 1
            state, metrics = base(state, batch, rng)
            if calls["n"] >= 3:
                bad = jnp.float32(float("nan"))
                metrics = {**metrics, "loss": bad,
                           "bad_step": jnp.int32(1)}
            return state, metrics

        log_every, lag = 2, 2
        trainer = make_trainer(nan_after_3, epochs=4, log_every=log_every,
                               metrics_lag=lag, n=320, batch=32)
        with pytest.raises(FloatingPointError, match="non-finite"):
            trainer.train()
        # abort within K*log_every of the bad step (K=2 here)
        assert calls["n"] - 3 <= lag + log_every

    def test_bad_step_flag_from_jitted_step(self):
        """make_train_step computes isfinite(loss) on device."""
        images, labels = synthetic_cls(8)
        model = MODELS.build("mnist_fcn", num_classes=4, dtype=jnp.float32)
        params = model.init(jax.random.key(0),
                            jnp.zeros((1, 16, 16, 1)))["params"]
        tx = build_optimizer(
            "sgd", build_schedule("constant", base_lr=0.1), params=params)
        state = TrainState.create(apply_fn=model.apply, params=params,
                                  tx=tx)
        step = make_train_step(make_loss_fn(), donate=False)
        batch = {"image": jnp.asarray(images), "label": jnp.asarray(labels)}
        _, metrics = step(state, batch, jax.random.key(0))
        assert int(metrics["bad_step"]) == 0
        bad_batch = {"image": jnp.full_like(batch["image"], jnp.nan),
                     "label": batch["label"]}
        _, metrics = step(state, bad_batch, jax.random.key(0))
        assert int(metrics["bad_step"]) == 1


class TestZeroSyncEval:
    def test_single_materialization_and_bitwise_totals(self):
        trainer = make_trainer(epochs=1)
        trainer.train()
        fetches_before = trainer.eval_fetches

        # reference: the old per-batch float() accumulation
        ref = defaultdict(float)
        for b in trainer.eval_loader:
            counts = trainer.eval_step(trainer.state, b)
            for k, v in counts.items():
                ref[k] += float(v)
        if "count" in ref and ref["count"] > 0:
            ref = {k: v / ref["count"] for k, v in ref.items()
                   if k != "count"}

        results = trainer.evaluate()
        assert trainer.eval_fetches == fetches_before + 1
        assert set(results) == set(ref)
        for k in ref:       # bitwise: same values, same summation order
            assert results[k] == ref[k], k


class TestLoaderDataWait:
    def test_parallel_loader_reports_wait(self):
        from deeplearning_tpu.data.loader import MapSource

        def slow_fetch(i):
            time.sleep(0.002)
            return {"x": np.full((3,), i, np.float32)}

        src = MapSource(24, slow_fetch)
        loader = DataLoader(src, 8, shuffle=False, num_workers=2,
                            lookahead=1)
        waits = []
        for _ in loader:
            assert loader.last_data_wait is not None
            waits.append(loader.last_data_wait)
        assert len(waits) == 3
        assert loader.data_wait_total == pytest.approx(sum(waits))
        # cold queue + slow decode: starvation must actually register
        assert max(waits) > 0

    def test_serial_loader_has_no_estimate(self):
        images, labels = synthetic_cls(32)
        loader = DataLoader(ArraySource(image=images, label=labels),
                            global_batch=16)
        next(iter(loader))
        assert loader.last_data_wait is None


class TestRetraceGuard:
    def test_warns_on_shape_churn(self):
        guard = RetraceGuard(jax.jit(lambda x: x * 2), name="churn_step")
        with warnings.catch_warnings():
            warnings.simplefilter("error")    # first call must NOT warn
            guard(jnp.ones((4, 4)))
        with pytest.warns(RuntimeWarning, match="retrace"):
            guard(jnp.ones((5, 4)))           # new shape -> warn
        assert guard.retraces == 1 and guard.n_signatures == 2
        with warnings.catch_warnings():
            warnings.simplefilter("error")    # known shape stays quiet
            guard(jnp.ones((4, 4)))

    def test_dtype_flip_warns_and_scalars_hash_by_type(self):
        guard = RetraceGuard(lambda x, n: x, name="s")
        guard(jnp.ones((2,), jnp.float32), 1)
        with warnings.catch_warnings():
            warnings.simplefilter("error")    # int value change: no warn
            guard(jnp.ones((2,), jnp.float32), 7)
        with pytest.warns(RuntimeWarning):
            guard(jnp.ones((2,), jnp.int32), 1)


class TestCompileCache:
    def test_cache_dir_env_wins_else_checkout(self, tmp_path, monkeypatch):
        import deeplearning_tpu.core.compile_cache as cc
        monkeypatch.delenv("DLTPU_COMPILE_CACHE", raising=False)
        # unset: the fixed <checkout>/.jax_cache, and idempotent
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(cc, "_enabled_dir", None)
        fixed = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache")
        assert cc.enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        assert cc.enable_compile_cache() == fixed
        # JAX_COMPILATION_CACHE_DIR set: JAX owns its flag (it read the
        # variable at import) and the library leaves it alone
        target = str(tmp_path / "cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", target)
        monkeypatch.setattr(cc, "_enabled_dir", None)
        assert cc.enable_compile_cache() == target
        assert cc.active_cache_dir() == target
        assert jax.config.jax_compilation_cache_dir == fixed

    def test_env_disable(self, monkeypatch):
        import deeplearning_tpu.core.compile_cache as cc
        monkeypatch.setenv("DLTPU_COMPILE_CACHE", "off")
        monkeypatch.setattr(cc, "_enabled_dir", None)
        assert cc.enable_compile_cache() is None


class TestStrictHotLoop:
    """Runtime proof of the sync-free claim (ISSUE 8): the counter-based
    tests above show ≤1 fetch per window; these run the same 5-step loop
    with ``analysis.strict``'s transfer-guard armed, so ANY stray D2H
    between log points would raise at the offending line."""

    def test_five_steps_under_dltpu_strict(self, monkeypatch):
        """Acceptance: 5-step CPU smoke under DLTPU_STRICT=1 passes with
        zero disallowed transfers between log points — every step region
        ran inside a guard section and the one designed sync (the lagged
        epoch-end drain) stayed outside them."""
        monkeypatch.setenv("DLTPU_STRICT", "1")
        trainer = make_trainer(epochs=1, log_every=100, n=5 * 16, batch=16)
        assert trainer.strict_modes == frozenset({"transfers"})
        trainer.train()
        assert trainer.strict_sections == 5   # guard wrapped every step
        assert trainer.deferred.fetched_entries == 5
        assert trainer.deferred.fetch_count <= 1

    def test_arg_overrides_env(self, monkeypatch):
        monkeypatch.setenv("DLTPU_STRICT", "1")
        trainer = make_trainer(epochs=1, log_every=100, n=16, batch=16,
                               strict=False)
        assert trainer.strict_modes == frozenset()
        trainer.train()
        assert trainer.strict_sections == 0

    def test_stray_sync_raises_when_enforced(self):
        """Negative case: a callback that materializes the in-flight
        metrics inside the guard region must raise. Only runnable where
        the backend enforces the d2h guard (CPU's zero-copy D2H is
        exempt from it, so this is a TPU/GPU-only teeth check)."""
        from deeplearning_tpu.analysis import strict
        from deeplearning_tpu.train.trainer import Callbacks
        if not strict.guard_enforced("device_to_host"):
            pytest.skip("backend does not enforce the d2h transfer "
                        "guard (CPU zero-copy)")
        cb = Callbacks()
        cb.register("after_iter",
                    lambda tr, metrics=None: float(metrics["loss"]))
        trainer = make_trainer(epochs=1, log_every=100, n=16, batch=16,
                               strict="transfers", callbacks=cb)
        with pytest.raises(Exception):
            trainer.train()
