"""Hook-structured Trainer — the ONE shared harness (SURVEY.md §1.1 goal).

Merges the three reference archetypes: the simple epoch loop
(classification/mnist/train.py:141), the yacs/DDP/AMP harness features
(swin main.py:84-300: accumulation, auto-resume, save-freq), and YOLOX's hook skeleton (yolox/core/trainer.py:69-88:
before_train/before_epoch/before_iter/after_iter/after_epoch/after_train)
with yolov5's Callbacks event registry (utils/callbacks.py:8).

The Trainer owns: the jitted steps, the loader epoch protocol
(set_epoch), metric meters, TB writer, Orbax checkpointing with best
tracking, EMA-evaluation, and hook dispatch. Everything device-side stays
in the jitted step functions it is given.
"""

from __future__ import annotations

import contextlib
import os
import time
from collections import defaultdict
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from ..analysis import strict as strict_mod
from ..core import rng as rng_mod
from ..core.checkpoint import CheckpointManager
from ..core.logging import (LoggerHub, MetricLogger,
                            TensorBoardWriter, create_logger,
                            is_main_process)
from ..data.device_prefetch import DevicePrefetcher
from ..elastic import faults
from ..elastic import heartbeat as hb
from ..elastic.preempt import (Preempted, PreemptionGuard,
                               agree_preempt_step)
from ..obs import flight
from ..obs import metrics as obs_metrics
from ..obs.spans import phase, span, step_span
from ..utils.profiling import RetraceGuard
from . import recovery as recovery_mod
from .async_metrics import DeferredMetrics
from .recovery import RecoveryExhausted, RecoveryManager, RecoveryPolicy

HOOKS = ("before_train", "after_train", "before_epoch", "after_epoch",
         "before_iter", "after_iter", "on_evaluate", "on_checkpoint")


class _DivergenceDetected(Exception):
    """Internal control flow: a lagged metrics entry surfaced a
    non-finite step. Carries the offending entry so the rollback path
    can report it; never escapes the Trainer."""

    def __init__(self, meta: Dict[str, Any], host: Dict[str, Any]):
        super().__init__(f"divergence at step {meta.get('step')}")
        self.meta = meta
        self.host = host


class Callbacks:
    """Named hook registry (yolov5 utils/callbacks.py surface)."""

    def __init__(self):
        self._hooks: Dict[str, List[Callable]] = defaultdict(list)

    def register(self, event: str, fn: Callable) -> None:
        if event not in HOOKS:
            raise KeyError(f"Unknown hook {event!r}; valid: {HOOKS}")
        self._hooks[event].append(fn)

    def fire(self, event: str, trainer: "Trainer", **kw) -> None:
        for fn in self._hooks[event]:
            fn(trainer, **kw)


class Trainer:
    def __init__(
        self, *,
        state,                                  # TrainState
        train_step: Callable,                   # (state, batch, rng)->...
        train_loader,
        eval_step: Optional[Callable] = None,   # (state, batch)->counts
        eval_loader=None,
        epochs: int = 1,
        seed: int = 0,
        log_every: int = 50,
        eval_every_epochs: int = 1,
        save_every_epochs: int = 1,
        workdir: Optional[str] = None,
        best_metric: str = "top1",
        callbacks: Optional[Callbacks] = None,
        metric_reducer: Optional[Callable[[Dict], Dict]] = None,
        abort_non_finite: bool = True,
        async_checkpoint: bool = False,
        log_backends=("tensorboard", "csv", "jsonl"),
        metrics_lag: Optional[int] = None,
        metrics_window: Optional[int] = None,
        retrace_warn: bool = True,
        prefetch="auto",
        obs="auto",
        run_config: Optional[Dict] = None,
        weight_update: Optional[str] = None,
        hbm_sample_s: float = 0.25,
        hbm_alert_frac: Optional[float] = None,
        preemptible: bool = True,
        heartbeat="auto",
        recovery=None,
        strict=None,
        metrics_port="auto",
    ):
        self.state = state
        # strict mode (README "Hot-loop sync policy"): arm JAX's own
        # sanitizers. "transfers" wraps every hot-loop step region in
        # transfer_guard_device_to_host("disallow") — a stray sync
        # between log points becomes a runtime error at the offending
        # line instead of a silent stall. "nans" arms jax_debug_nans
        # for the whole run. None defers to DLTPU_STRICT in the env.
        self.strict_modes = strict_mod.resolve(strict)
        self.strict_sections = 0     # guard regions entered (test hook)
        # "threads" arms the runtime thread sanitizer now, before the
        # prefetcher/heartbeat/metrics objects construct their locks —
        # enable() patches module threading attrs, so timing matters
        strict_mod.maybe_enable_threads(self.strict_modes)
        # self-healing policy (README "Self-healing policy"): None/"abort"
        # keeps the seed behavior (abort_non_finite raises on the first
        # bad step); "rollback" (or a RecoveryPolicy / RecoveryManager)
        # rolls back to a device-side anchor, skips the bad data window,
        # and dampens updates through a cooldown — aborting only once
        # the rollback budget is spent.
        if recovery is None or recovery == "abort":
            self._recovery: Optional[RecoveryManager] = None
        elif recovery == "rollback":
            self._recovery = RecoveryManager(RecoveryPolicy())
        elif isinstance(recovery, RecoveryPolicy):
            self._recovery = (RecoveryManager(recovery)
                              if recovery.mode == "rollback" else None)
        elif isinstance(recovery, RecoveryManager):
            self._recovery = recovery
        else:
            raise ValueError(f"recovery must be None|'abort'|'rollback'|"
                             f"RecoveryPolicy|RecoveryManager, "
                             f"got {recovery!r}")
        # elastic-run wiring (README "Elastic run policy"): preemptible
        # installs the chained SIGTERM/SIGINT guard (flush checkpoint →
        # Preempted at the next step boundary → exit 75); heartbeat
        # "auto" writes the supervisor's step/activity watermark file
        # when DLTPU_HEARTBEAT names one (a path forces it, False/None
        # disables).
        self.preemptible = bool(preemptible)
        self._heartbeat_opt = heartbeat
        self.preempt_guard: Optional[PreemptionGuard] = None
        self._beat: Optional[hb.Heartbeat] = None
        self._beat_writer: Optional[hb.HeartbeatWriter] = None
        self.hbm_alert_frac = hbm_alert_frac
        # observability (README "Observability policy"): spans + flight
        # recorder + HBM sampler. "auto" = on whenever the run has a
        # workdir to dump trace.json/flightrec.json into; True forces it
        # (tests), False disables. Retrace warnings always land in the
        # flight ring — recording is bounded and sync-free.
        self.obs_enabled = bool(workdir) if obs == "auto" else bool(obs)
        self.run_config = run_config
        # weight-update sharding mode ("replicated"/"zero1"), recorded in
        # every checkpoint's topology sidecar; None lets the sidecar
        # infer it from the state's moment/param layouts
        self.weight_update = weight_update
        self.hbm_sample_s = hbm_sample_s
        self._hbm = None
        self._obs_owns_tracer = False
        self._obs_started = False
        self._stop_flight_watch = None
        # fleet scrape surface: "auto" serves /metrics + /healthz only
        # when DLTPU_METRICS_PORT names a port (the supervisor/fleet
        # contract); an int forces that port (0 = ephemeral); None/False
        # disables. Train replicas then answer the same probes serve
        # replicas do.
        if metrics_port == "auto":
            raw = os.environ.get("DLTPU_METRICS_PORT")
            self.metrics_port = int(raw) if raw not in (None, "") else None
        else:
            self.metrics_port = (int(metrics_port)
                                 if metrics_port not in (None, False)
                                 else None)
        self._metrics_server = None
        self._owns_metrics_registry = False
        self.train_step = (RetraceGuard(
            train_step, name="train_step",
            on_retrace=lambda info: flight.record("retrace", **info))
            if retrace_warn else train_step)
        # overlapped device feed (see README "Input feed & donation
        # policy"): with a mesh-bearing loader the serial host→HBM
        # transfer is the hot loop's last blocking stage, so auto-wrap it
        # in a DevicePrefetcher. prefetch="auto" wraps only mesh loaders;
        # an int wraps any epoch-protocol loader at that depth; 0/None
        # disables wrapping.
        self.train_loader = self._wrap_prefetch(train_loader, prefetch)
        self.eval_step = eval_step
        self.eval_loader = eval_loader
        self.epochs = epochs
        self.log_every = log_every
        self.eval_every = eval_every_epochs
        self.save_every = save_every_epochs
        self.best_metric = best_metric
        self.best_value = float("-inf")
        self.callbacks = callbacks or Callbacks()
        self.metric_reducer = metric_reducer
        self.abort_non_finite = abort_non_finite
        self.workdir = workdir
        self.logger = create_logger("dltpu", workdir)
        # pluggable backends (yolov5 Loggers shape): tensorboard + csv +
        # offline-W&B jsonl by default; self.tb stays the TB handle for
        # figures/images
        self.hub = LoggerHub(workdir, log_backends)
        self.tb = self.hub.tb
        self.meters = MetricLogger()
        self.rng = rng_mod.host_key(seed)
        self.epoch = 0
        # sync-free hot loop (see README "Hot-loop sync policy"): every
        # step's device-scalar metrics are enqueued here and only entries
        # at least metrics_lag steps old are ever fetched — by then they
        # are resolved, so the fetch never stalls the dispatch queue.
        # Default lag = log_every: at each log point the previous log
        # window is ready, so divergence aborts within 2*log_every steps.
        self.metrics_lag = (metrics_lag if metrics_lag is not None
                            else log_every)
        # windowed on-device reduction: at log_every ≫ 100 holding (and
        # fetching) one scalar dict PER STEP is the remaining O(log_every)
        # host cost, so auto-fold the window into a device-resident
        # running mean (one fused add per push). None = auto threshold;
        # 0 disables; an int forces that window.
        self.metrics_window = (metrics_window if metrics_window is not None
                               else (log_every if log_every > 100 else 0))
        self.deferred = DeferredMetrics(lag=self.metrics_lag,
                                        window=self.metrics_window or None)
        self.eval_fetches = 0        # host materializations per evaluate()
        self._host_step: Optional[int] = None  # host mirror of state.step
        self._batches = None         # live epoch iterator (rollback hook)
        self._aot_step = None        # the AOT-compiled step (precompile)
        self._stop_requested = False
        self.ckpt = (CheckpointManager(f"{workdir}/ckpt",
                                       async_save=async_checkpoint)
                     if workdir else None)

    @property
    def host_step(self) -> int:
        """Host-side step counter mirroring ``state.step`` without a
        per-use D2H fetch; seeded once (from the restored state) and
        incremented in lockstep with train_step calls."""
        if self._host_step is None:
            try:
                self._host_step = int(getattr(self.state, "step", 0))
            except TypeError:
                self._host_step = 0
        return self._host_step

    # ----------------------------------------------------- device feed
    @staticmethod
    def _wrap_prefetch(loader, prefetch):
        if loader is None or not prefetch:
            return loader
        if isinstance(loader, DevicePrefetcher):
            return loader                     # caller already wrapped it
        if prefetch == "auto":
            # only wrap loaders that own a mesh (their batches need the
            # make_global_array assembly the prefetcher hides) and speak
            # the epoch protocol the wrapper must preserve
            if getattr(loader, "mesh", None) is None or \
                    not hasattr(loader, "set_epoch"):
                return loader
            depth = 2
        else:
            depth = int(prefetch)
        return DevicePrefetcher(loader, depth=depth)

    def precompile(self):
        """AOT step warmup: compile the train step against the loader's
        ABSTRACT batch spec (``element_spec``) before any data exists —
        ``jit(...).lower(...).compile()`` lands the executable in jit's
        cache and the persistent compile cache (``core/compile_cache``),
        so the first real step dispatches instead of serializing a
        multi-minute XLA compile after the first batch arrives.

        When the train loader is a DevicePrefetcher, its worker thread
        is started FIRST, so first-batch decode + H2D transfer fill the
        queue while XLA compiles on this thread. Returns compile seconds,
        or None when the loader/step has no AOT surface."""
        from ..core.compile_cache import enable_compile_cache
        enable_compile_cache()
        self._obs_start()      # the compile span belongs on the timeline
        if hasattr(self.train_loader, "start"):
            self.train_loader.start()         # overlap feed with compile
        spec_fn = getattr(self.train_loader, "element_spec", None)
        batch_spec = spec_fn() if spec_fn is not None else None
        if batch_spec is None:
            return None
        # unwrap the RetraceGuard to reach the jitted function's .lower
        fn = getattr(self.train_step, "fn", self.train_step)
        if not hasattr(fn, "lower"):
            return None
        from ..obs.xla import tracked_compile
        t0 = time.perf_counter()
        # trace + lower and the XLA compile are timed apart: the phase
        # here, ``compile/train_step`` inside tracked_compile
        with phase("setup/lower"):
            lowered = fn.lower(self.state, batch_spec, self.rng)
        self._aot_step = tracked_compile(lowered, "train_step")
        dt = time.perf_counter() - t0
        self.precompile_seconds = dt
        self.logger.info(f"precompile: train step AOT-compiled in "
                         f"{dt:.2f}s (overlapped with feed warmup)")
        return dt

    def compiled_step_text(self) -> Optional[str]:
        """Text of the AOT-compiled train step (its ``op_name`` metadata
        carries the Flax module paths a device trace's op events lack);
        None before ``precompile()`` has compiled one."""
        return None if self._aot_step is None else self._aot_step.as_text()

    def close_feed(self) -> None:
        """Shut down the live epoch iterator's prefetch pipeline (its
        worker thread), for a caller that leaves an epoch part-way."""
        close = getattr(self._batches, "close", None)
        if close is not None:
            close()

    def request_stop(self) -> None:
        """Ask ``train()`` to return: the flag is looked at once per step,
        at the elastic step boundary, after which the epoch's metrics are
        drained, the feed is closed, ``after_train`` fires and ``train()``
        returns the state (no further eval or checkpoint). Safe from a
        callback or another thread."""
        self._stop_requested = True

    # ----------------------------------------------------- observability
    def _obs_config(self) -> Dict[str, Any]:
        """Run config embedded in flightrec.json: the caller's full cfg
        when provided (tools/train.py), else the Trainer's own knobs."""
        if self.run_config is not None:
            return self.run_config
        return {"epochs": self.epochs, "log_every": self.log_every,
                "metrics_lag": self.metrics_lag,
                "metrics_window": self.metrics_window,
                "best_metric": self.best_metric,
                "workdir": self.workdir}

    def _obs_start(self) -> None:
        """Idempotent: called from both ``precompile()`` (so the AOT
        compile span lands on the timeline) and ``train()``."""
        if not self.obs_enabled or self._obs_started:
            return
        self._obs_started = True
        from ..obs import spans
        from ..obs.xla import HbmWatermark
        self._obs_owns_tracer = not spans.enabled()
        spans.enable()
        if self.workdir:
            flight.configure(os.path.join(self.workdir, "flightrec.json"),
                             config=self._obs_config())
            flight.install_signal_handler()
            self._stop_flight_watch = flight.watch_pending()
        self._hbm = HbmWatermark(interval_s=self.hbm_sample_s,
                                 alert_frac=self.hbm_alert_frac).start()
        # metrics registry: always on with obs (the push helpers in
        # _consume/feed/recovery need a home); the HTTP scrape server
        # only when a port was asked for
        self._owns_metrics_registry = not obs_metrics.enabled()
        obs_metrics.enable()
        if self.metrics_port is not None and self._metrics_server is None:
            self._metrics_server = obs_metrics.MetricsServer(
                port=self.metrics_port,
                healthz_fn=self._metrics_healthz).start()
            obs_metrics.write_endpoint(self._metrics_server.url,
                                       role="train")

    def _metrics_healthz(self):
        """Train-replica health: backed by the elastic heartbeat — the
        same step/activity watermark the supervisor's wedge detector
        reads, so /healthz and the heartbeat file never disagree."""
        payload = {"status": "ready", **obs_metrics.replica_identity()}
        if self._beat is not None:
            payload["step"] = self._beat.step
            payload["activity"] = self._beat.activity
            payload["phase"] = self._beat.phase
        return 200, payload

    def _obs_finish(self) -> None:
        if not self.obs_enabled:
            return
        from ..obs import spans
        if self._hbm is not None:
            self._hbm.stop()
            self.hbm_watermark = self._hbm.watermark()
        if self._stop_flight_watch is not None:
            self._stop_flight_watch()
            self._stop_flight_watch = None
        tracer = spans.get_tracer()
        if tracer is not None and self.workdir:
            tracer.dump(os.path.join(self.workdir, "trace.json"))
        if self._obs_owns_tracer:
            spans.disable()
        if self._metrics_server is not None:
            self._metrics_server.stop()
            self._metrics_server = None
        reg = obs_metrics.get_registry()
        if reg is not None and self.workdir:
            reg.dump(os.path.join(self.workdir, "metrics_registry.json"))
        if self._owns_metrics_registry:
            obs_metrics.disable()
        self._obs_started = False      # a second train() re-arms

    # ---------------------------------------------------------- elastic
    def _elastic_start(self) -> None:
        """Arm the preemption guard and the heartbeat writer. Idempotent
        like ``_obs_start`` (train() may be called twice)."""
        if self.preemptible and self.preempt_guard is None:
            guard = PreemptionGuard()
            if self.ckpt:
                # in-handler flush: the in-flight async write commits
                # even if the loop never reaches another step boundary
                guard.add_flush(self.ckpt.flush)
            if guard.install():
                self.preempt_guard = guard
        if self._beat_writer is None:
            path = self._heartbeat_opt
            if path == "auto":
                path = os.environ.get(hb.ENV_VAR)
            if path:
                self._beat = hb.Heartbeat(step=self.host_step)
                self._beat_writer = hb.HeartbeatWriter(
                    str(path), self._beat).start()

    def _elastic_finish(self) -> None:
        if self._beat_writer is not None:
            self._beat_writer.stop()
            self._beat_writer = None
        if self.preempt_guard is not None:
            self.preempt_guard.uninstall()
            self.preempt_guard = None

    def _beat_touch(self, phase: str) -> None:
        if self._beat is not None:
            self._beat.touch(phase, step=self.host_step)

    def _check_preempted(self) -> None:
        """Step-boundary poll (one Event.is_set when armed)."""
        # a SIGTERM handler defers its flight dump to here (the signal-
        # handler-safety contract: no open()/json on the signal stack)
        if self.obs_enabled:
            flight.flush_pending()
        if self.preempt_guard is not None and \
                self.preempt_guard.requested():
            raise Preempted(
                f"preemption signal at step {self.host_step}",
                signum=self.preempt_guard.signum, step=self.host_step)

    def _on_preempted(self, exc: Preempted) -> None:
        """Land the final state: checkpoint the interrupted step (unless
        a periodic save already wrote it), barrier the write, dump the
        flight ring with the distinct 'preempted' reason."""
        if self.ckpt:
            # sync is fine — we're dying; on a pod, agree on process 0's
            # step so every host lands the SAME checkpoint step even
            # when the pod-wide SIGTERM hit different step boundaries
            step = agree_preempt_step(int(self.state.step))
            if self.ckpt.latest_step() != step:
                self._save()
            self.ckpt.flush()
            self.logger.info(
                f"preempted (signal {exc.signum}): checkpoint flushed at "
                f"step {step}; exit with EXIT_PREEMPTED requeues")
        if self.obs_enabled:
            flight.dump("preempted", exception=exc)

    # ------------------------------------------------------------- train
    def _strict_ctx(self):
        """One hot-loop guard region (see ``analysis.strict``). Counted
        so tests can assert the guard really wrapped every step."""
        if "transfers" in self.strict_modes:
            self.strict_sections += 1
            return strict_mod.no_host_transfers()
        return contextlib.nullcontext()

    def train(self) -> Any:
        self._obs_start()
        self._elastic_start()
        try:
            if "nans" in self.strict_modes:
                # run-wide, not per-section: jax_debug_nans changes what
                # XLA compiles, so toggling it per step would retrace
                with strict_mod.debug_nans():
                    return self._train()
            return self._train()
        except Preempted as exc:
            self._on_preempted(exc)
            raise
        except BaseException as exc:
            if self.obs_enabled:
                reason = ("divergence"
                          if isinstance(exc, FloatingPointError)
                          else "exception")
                flight.dump(reason, exception=exc)
            raise
        finally:
            self._elastic_finish()
            self._obs_finish()

    def _train(self) -> Any:
        if self.ckpt:
            restored, step = self.ckpt.auto_resume(self.state)
            if step:
                self.state = restored
                steps_per_epoch = max(len(self.train_loader), 1)
                self.epoch = int(step) // steps_per_epoch
                self._host_step = int(step)
        if self._recovery is not None:
            # fresh init or just-restored checkpoint: both known-clean
            self._recovery.seed(self.host_step, self.state)
        self.callbacks.fire("before_train", self)
        try:
            for epoch in range(self.epoch, self.epochs):
                self.epoch = epoch
                self.callbacks.fire("before_epoch", self)
                self._train_one_epoch(epoch)
                if self._stop_requested:
                    self._stop_requested = False   # a later train() runs
                    self.close_feed()
                    break
                self.callbacks.fire("after_epoch", self)
                if self.eval_step and self.eval_loader is not None and \
                        (epoch + 1) % self.eval_every == 0:
                    self.evaluate()
                if self.ckpt and (epoch + 1) % self.save_every == 0:
                    self._save()
        finally:
            # land any in-flight async write + pending best-copy even on
            # abort (non-finite guard, preemption) BEFORE callbacks that
            # might read the best dir
            if self.ckpt:
                self.ckpt.wait_until_finished()
        self.callbacks.fire("after_train", self)
        if self._recovery is not None and self._recovery.rollbacks \
                and self.obs_enabled:
            # the run SURVIVED its divergences — land the evidence in
            # flightrec.json even though nothing crashed
            flight.record("recovery_summary", **self._recovery.stats())
            flight.dump("recovered")
        # self.epochs, not self.epoch: the loop leaves self.epoch at the
        # last INDEX (epochs-1), and summary only runs on normal exit
        summary = {"epochs": self.epochs, **getattr(self, "_last_eval", {})}
        # omit when the metric never updated (no eval loader): -inf would
        # serialize as the non-standard JSON token -Infinity
        if self.best_value != float("-inf"):
            summary["best_" + self.best_metric] = self.best_value
        self.hub.summary(summary)
        self.hub.close()
        return self.state

    def _train_one_epoch(self, epoch: int) -> None:
        """One epoch, retried through divergence rollbacks: each
        ``_DivergenceDetected`` rolls the state back to the anchor and
        replays the epoch under a fresh loader permutation (the skip) —
        the budget inside ``_rollback`` bounds the retries."""
        while True:
            try:
                return self._epoch_pass(epoch)
            except _DivergenceDetected as d:
                self._rollback(d)

    def _epoch_pass(self, epoch: int) -> None:
        """Sync-free hot loop: the only host↔device round-trips are the
        lagged fetches inside ``self.deferred`` (entries ≥ metrics_lag
        steps old, already resolved) — never the in-flight step."""
        self.train_loader.set_epoch(epoch)
        self.host_step          # seed the host mirror before the loop
        n_iter = len(self.train_loader)
        t_data = time.time()
        batches = iter(self.train_loader)
        # kept for the rollback path: an abandoned pass must shut its
        # prefetch pipeline down instead of leaking the worker thread
        self._batches = batches
        it = 0
        while True:
            # data-wait phase: host blocked on the (possibly prefetched)
            # loader — on the span timeline this is the slice the feed
            # follow-ups in ROADMAP.md need to see shrink
            with span("data_wait", epoch=epoch,
                      step=self.host_step) as waited:
                try:
                    batch = next(batches)
                except StopIteration:
                    break
                # the feed's number for this batch: joins this wait and
                # the step to the worker's feed/* spans of the same batch
                fed = getattr(self.train_loader, "last_batch", None)
                if fed is not None:
                    waited.args["batch"] = fed
            wall_wait = time.time() - t_data
            # prefer the loader's own queue-empty estimate (actual
            # starvation) over wall-clock-between-iterations, which
            # includes step dispatch time
            loader_wait = getattr(self.train_loader, "last_data_wait",
                                  None)
            data_time = loader_wait if loader_wait is not None else \
                wall_wait
            # strict region: under Trainer(strict="transfers") /
            # DLTPU_STRICT=1 everything from before_iter through the
            # deferred push runs under a d2h transfer-guard — the lagged
            # metrics poll below stays OUTSIDE it, because that fetch is
            # the one designed sync per log window
            with self._strict_ctx():
                self.callbacks.fire("before_iter", self, batch=batch)
                # recovery hooks, dispatched BEFORE the (possibly
                # donating) step consumes the state buffers: the periodic
                # device-side anchor snapshot, and — inside a
                # post-rollback cooldown — a params copy for the damped
                # update below
                prev_params = cooldown = None
                if self._recovery is not None:
                    self._recovery.maybe_snapshot(self.host_step,
                                                  self.state)
                    cooldown = self._recovery.cooldown_scale(
                        self.host_step)
                    if cooldown is not None:
                        prev_params = recovery_mod.snapshot_state(
                            self.state.params)
                # dispatch phase: enqueue the jitted step (async — this
                # span measures host dispatch, not device compute; a
                # device trace is aligned to the k-th of these afterwards)
                with step_span("dispatch", self.host_step):
                    self.state, metrics = self.train_step(
                        self.state, batch, self.rng)
                if cooldown is not None:
                    # shrink this step's param delta (exact LR decay for
                    # SGD); optimizer moments keep their own schedule
                    self.state = self.state.replace(
                        params=recovery_mod.damp_update(
                            prev_params, self.state.params, cooldown))
                self.callbacks.fire("after_iter", self, metrics=metrics)
                self._host_step = self.host_step + 1
                self.deferred.push(metrics, epoch=epoch, it=it,
                                   step=self.host_step, n_iter=n_iter,
                                   data_time=data_time)
            if it % self.log_every == 0:
                with span("metrics_flush"):
                    self._consume(self.deferred.poll())
            # elastic step boundary: advance the heartbeat watermark,
            # give the fault harness its mid-step hook (a sigterm fault
            # routes through the real kernel-delivered handler chain),
            # then land any requested preemption while state is clean
            self._beat_touch("step")
            faults.maybe_fire("step", step=self.host_step)
            if faults.consume("nan", "step", step=self.host_step):
                # poison the params so the NEXT step's loss goes NaN
                # through the real jitted bad_step path — divergence
                # detection and recovery run end to end, not shortcut
                self.state = recovery_mod.poison_state(self.state)
            self._check_preempted()
            if self._stop_requested:
                break
            t_data = time.time()
            it += 1
        # epoch-end barrier: one bulk fetch lands every remaining entry,
        # so short epochs still log and a NaN in the tail still aborts
        with span("metrics_flush", drain=True):
            self._consume(self.deferred.drain())
        # feed telemetry (DevicePrefetcher): queue occupancy + H2D wait
        # land next to the train scalars so an input-bound epoch is
        # visible without a profiler
        feed_stats = getattr(self.train_loader, "stats", None)
        if feed_stats is not None:
            stats = feed_stats()
            self.hub.scalars({f"feed/{k}": v for k, v in stats.items()},
                             self.host_step)
            if self.obs_enabled:
                flight.record("feed", epoch=epoch, **stats)
                for k, v in stats.items():
                    if isinstance(v, (int, float)):
                        obs_metrics.set_gauge(f"dltpu_feed_{k}", float(v))
            reset = getattr(self.train_loader, "reset_stats", None)
            if reset is not None:
                reset()

    def _consume(self, entries) -> None:
        """Divergence-check every materialized entry, then log the
        newest one (the stale snapshot that stands in for 'now')."""
        if not entries:
            return
        if self.obs_enabled:
            # flight ring: one structured snapshot per materialized
            # entry, so a crash dump carries the last-K step metrics
            for meta, host in entries:
                flight.record("step", step=meta.get("step"),
                              epoch=meta.get("epoch"), it=meta.get("it"),
                              data_time=meta.get("data_time"),
                              metrics=host)
        if self._recovery is not None or self.abort_non_finite:
            bad_i = None
            for i, (meta, host) in enumerate(entries):
                # bad_step is the jitted isfinite(loss) flag; the loss
                # check is the fallback for custom steps that don't
                # provide it (non-finite params keep it latched anyway)
                if host.get("bad_step", 0) > 0 or not np.isfinite(
                        host.get("loss", 0.0)):
                    bad_i = i
                    break
            if self._recovery is not None and bad_i != 0:
                # the newest verified-finite step vouches for every
                # pending anchor snapshot strictly older than it
                clean_meta = entries[len(entries) - 1 if bad_i is None
                                     else bad_i - 1][0]
                if clean_meta.get("step") is not None:
                    self._recovery.mark_verified(clean_meta["step"])
            if bad_i is not None:
                meta, host = entries[bad_i]
                self.logger.error(
                    f"Loss is {host.get('loss')}, "
                    + ("recovering" if self._recovery is not None
                       else "stopping training")
                    + f" (epoch {meta['epoch']} it {meta['it']})")
                if self.obs_enabled:
                    flight.record("divergence",
                                  step=meta.get("step"),
                                  epoch=meta["epoch"],
                                  it=meta["it"],
                                  loss=host.get("loss"))
                if self._recovery is not None:
                    raise _DivergenceDetected(meta, host)
                raise FloatingPointError(
                    f"non-finite loss {host.get('loss')} at epoch "
                    f"{meta['epoch']} it {meta['it']}")
        meta, host = entries[-1]
        host = {k: v for k, v in host.items() if k != "bad_step"}
        host["data_time"] = meta["data_time"]
        self.meters.update(**host)
        self.logger.info(
            f"epoch {meta['epoch']} it {meta['it']}/{meta['n_iter']} "
            f"{self.meters}")
        self.hub.scalars({f"train/{k}": v for k, v in host.items()},
                         meta["step"])
        # scrape surface: the same lagged (already-resolved) snapshot —
        # no extra D2H, the fleet sees exactly what the log line sees
        if meta.get("step") is not None:
            obs_metrics.set_gauge("dltpu_train_step", float(meta["step"]))
        for k, v in host.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                safe = "".join(c if c.isalnum() else "_" for c in str(k))
                obs_metrics.set_gauge(f"dltpu_train_{safe}", float(v))

    # ---------------------------------------------------------- recovery
    def _rollback(self, d: _DivergenceDetected) -> None:
        """Roll back to the anchor, skip the offending data window, and
        arm the cooldown — or, with the budget spent, fall through to
        the seed abort path (FloatingPointError, same message shape)."""
        meta, host = d.meta, d.host
        bad_step = int(meta.get("step") or self.host_step)
        # the failed pass's prefetch pipeline must die before we restart
        self.close_feed()
        try:
            anchor_step, state = self._recovery.on_divergence(bad_step)
        except RecoveryExhausted as exc:
            if self.obs_enabled:
                flight.record("recovery_exhausted", step=bad_step,
                              error=str(exc), **self._recovery.stats())
            raise FloatingPointError(
                f"non-finite loss {host.get('loss')} at epoch "
                f"{meta['epoch']} it {meta['it']} ({exc})") from exc
        self.state = state
        self._host_step = anchor_step
        # in-flight entries were computed from poisoned state — replace
        # the ring instead of materializing them
        self.deferred = DeferredMetrics(lag=self.metrics_lag,
                                        window=self.metrics_window or None)
        # skip the window: a reseed-capable loader replays the epoch
        # under a fresh permutation, so the poisonous batch order is
        # never retraced verbatim
        reseed = getattr(self.train_loader, "reseed", None)
        if reseed is not None:
            reseed(self._recovery.rollbacks)
        pol = self._recovery.policy
        self.logger.warning(
            f"divergence at step {bad_step} (loss {host.get('loss')}): "
            f"rolled back to step {anchor_step}, "
            + ("reseeded loader, " if reseed is not None else "")
            + f"lr x{pol.lr_decay} for {pol.cooldown_steps} steps "
            f"({len(self._recovery.recovery_steps)}/{pol.max_recoveries} "
            f"recoveries used)")
        obs_metrics.inc("dltpu_recovery_rollbacks_total")
        if self.obs_enabled:
            flight.record("recovery", step=bad_step,
                          anchor_step=anchor_step, loss=host.get("loss"),
                          epoch=meta.get("epoch"),
                          rollbacks=self._recovery.rollbacks,
                          skipped=[anchor_step, bad_step],
                          cooldown_steps=pol.cooldown_steps,
                          lr_decay=pol.lr_decay,
                          reseeded=reseed is not None)
        self._beat_touch("recovery")

    # -------------------------------------------------------------- eval
    def evaluate(self) -> Dict[str, float]:
        """Zero-sync eval: every batch's count dict stays on device while
        the loop runs (dispatch only), then ONE ``jax.device_get`` lands
        the whole list. Host-side accumulation order matches the old
        per-batch-float path exactly, so totals are bitwise identical."""
        self._beat_touch("eval")
        with span("eval", epoch=self.epoch):
            per_batch = [self.eval_step(self.state, batch)
                         for batch in self.eval_loader]
            # the one materialization
            # dltpu: allow(DLT100) designed: single bulk D2H per eval pass
            host_counts = jax.device_get(per_batch)
        self._beat_touch("eval")
        self.eval_fetches += 1
        totals: Dict[str, float] = defaultdict(float)
        for counts in host_counts:
            for k, v in counts.items():
                totals[k] += float(v)
        results = dict(totals)
        if self.metric_reducer:
            results = self.metric_reducer(results)
        elif "count" in totals and totals["count"] > 0:
            results = {k: v / totals["count"] for k, v in totals.items()
                       if k != "count"}
        self._last_eval = dict(results)
        self.callbacks.fire("on_evaluate", self, results=results)
        self.logger.info(f"eval @ epoch {self.epoch}: "
                         + "  ".join(f"{k}={v:.4f}"
                                     for k, v in results.items()))
        self.hub.scalars({f"eval/{k}": v for k, v in results.items()},
                         self.host_step)
        value = results.get(self.best_metric)
        if value is not None and value > self.best_value:
            self.best_value = value
            if self.ckpt:
                self._save(is_best=True)
        return results

    def _save(self, is_best: bool = False) -> None:
        step = int(self.state.step)
        self._beat_touch("checkpoint")
        faults.maybe_fire("checkpoint", step=step)
        with span("checkpoint", step=step, best=is_best):
            self.ckpt.save(step, self.state,
                           metrics={self.best_metric: self.best_value},
                           is_best=is_best,
                           topology=self._topology())
        if faults.consume("ckpt_corrupt", "checkpoint", step=step):
            # flush FIRST so the checksum sidecar records the intact
            # files — the bit-flip after commit is exactly the silent
            # on-disk corruption restore-time verification must catch
            self.ckpt.flush()
            hit = faults.corrupt_checkpoint(self.ckpt.directory, step)
            self.logger.warning(
                f"fault: corrupted checkpoint step {step} "
                f"({len(hit)} file(s))")
        self.callbacks.fire("on_checkpoint", self, step=step)

    def _topology(self) -> Optional[Dict[str, Any]]:
        """Topology fingerprint for the checkpoint sidecar — what a
        cross-topology resume reports it is re-sharding FROM."""
        try:
            from ..elastic.topology import current_topology
            return current_topology(state=self.state,
                                    weight_update=self.weight_update)
        except Exception:  # noqa: BLE001 - never block a save on it
            return None
