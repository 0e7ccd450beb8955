"""PR 5 observability: span tracer / compile telemetry / flight
recorder units, the Trainer trace + crash-dump acceptance runs, the
serving health surface, and the satellite fixes (create_logger dir
cache, RetraceGuard hook + signature semantics,
obs_report --check)."""

import json
import logging
import os
import subprocess
import sys
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))

from deeplearning_tpu.core.registry import MODELS
from deeplearning_tpu.data import ArraySource, DataLoader
from deeplearning_tpu.obs import flight, spans
from deeplearning_tpu.obs import xla as obs_xla
from deeplearning_tpu.obs.flight import FlightRecorder
from deeplearning_tpu.obs.spans import SpanTracer, span, step_span
from deeplearning_tpu.train import (TrainState, make_eval_step,
                                    make_train_step)
from deeplearning_tpu.train.classification import make_loss_fn, make_metric_fn
from deeplearning_tpu.train.optim import build_optimizer
from deeplearning_tpu.train.schedules import build_schedule
from deeplearning_tpu.train.trainer import Trainer
from deeplearning_tpu.utils.profiling import RetraceGuard


@pytest.fixture(autouse=True)
def _clean_obs_globals():
    """Every test starts and ends with the process-wide tracer disabled
    and the default flight recorder disarmed."""
    spans.disable()
    rec = flight.get_recorder()
    rec.clear()
    rec.path = None
    rec.config = None
    yield
    spans.disable()
    rec = flight.get_recorder()
    rec.clear()
    rec.path = None
    rec.config = None


# ------------------------------------------------------------ span tracer
class TestSpanTracer:
    def test_disabled_span_is_inert(self):
        assert not spans.enabled()
        with span("data_wait", epoch=0):
            pass                               # no tracer: nothing breaks
        assert spans.get_tracer() is None

    def test_spans_carry_thread_and_args(self):
        tracer = spans.enable()
        with span("data_wait", epoch=3):
            time.sleep(0.001)
        events = tracer.events()
        metas = [e for e in events if e["ph"] == "M"]
        xs = [e for e in events if e["ph"] == "X"]
        assert metas and metas[0]["name"] == "thread_name"
        assert len(xs) == 1
        ev = xs[0]
        assert ev["name"] == "data_wait"
        assert ev["dur"] >= 1000                # >= 1ms in microseconds
        assert ev["args"] == {"epoch": 3}

    def test_enable_is_idempotent(self):
        t1 = spans.enable()
        t2 = spans.enable()
        assert t1 is t2

    def test_dump_is_chrome_trace_json(self, tmp_path):
        tracer = spans.enable()
        with span("dispatch"):
            pass
        tracer.record("marker", time.perf_counter(), 0.0, {"k": 1})
        path = tracer.dump(str(tmp_path / "nested" / "trace.json"))
        with open(path) as f:
            doc = json.load(f)
        assert isinstance(doc["traceEvents"], list)
        phs = {e["ph"] for e in doc["traceEvents"]}
        assert {"M", "X", "i"} <= phs
        inst = [e for e in doc["traceEvents"] if e["ph"] == "i"]
        assert inst[0]["s"] == "t"
        assert doc["otherData"]["recorded"] == 2

    def test_ring_is_bounded_and_counts_drops(self):
        tracer = SpanTracer(capacity=4)
        for i in range(10):
            tracer.record(f"s{i}", time.perf_counter(), 0.0)
        assert tracer.recorded == 10
        assert tracer.dropped == 6
        assert len([e for e in tracer.events() if e["ph"] != "M"]) == 4

    def test_step_span_carries_the_step_beside_a_plain_span(self):
        tracer = spans.enable()
        with step_span("dispatch", 7):
            pass
        with span("my_phase"):
            pass
        names = [e["name"] for e in tracer.events() if e["ph"] == "X"]
        assert "dispatch" in names and "my_phase" in names
        disp = next(e for e in tracer.events()
                    if e["ph"] == "X" and e["name"] == "dispatch")
        assert disp["args"] == {"step": 7}

    def test_step_span_is_inert_when_disabled(self):
        with step_span("dispatch", 3) as s:
            s.args["late"] = True               # args may be added inside
        assert spans.get_tracer() is None       # it switched nothing on
        tracer = spans.enable()
        assert [e for e in tracer.events() if e["ph"] == "X"] == []


# ---------------------------------------------------------- run phases
class TestPhases:
    @pytest.mark.parametrize("ring_on", [False, True])
    def test_phase_always_lands_in_the_flight_ring(self, ring_on):
        tracer = spans.enable() if ring_on else None
        t_before = time.perf_counter()
        with spans.phase("setup/model_init", model="tiny"):
            time.sleep(0.002)
        (ev,) = spans.phases()
        assert ev["kind"] == "phase" and ev["name"] == "setup/model_init"
        assert ev["seconds"] >= 0.002
        assert t_before <= ev["t0"] <= time.perf_counter()
        assert ev["model"] == "tiny"
        if ring_on:
            (x,) = [e for e in tracer.events() if e["ph"] == "X"]
            assert x["name"] == "setup/model_init"
            assert x["dur"] == pytest.approx(ev["seconds"] * 1e6, rel=1e-3)
        else:
            assert spans.get_tracer() is None   # recording enabled nothing

    def test_phase_records_when_the_block_raises(self):
        with pytest.raises(KeyError):
            with spans.phase("setup/data"):
                raise KeyError("no such file")
        assert [e["name"] for e in spans.phases()] == ["setup/data"]

    def test_phases_outlive_the_flight_rings_eviction(self):
        # a long run with obs on: one ``step`` event a step pushes the
        # set-up's phases out of the ring of 256; ``phases()`` still has them
        with spans.phase("setup/model_init"):
            pass
        spans.record_phase("setup/lower", time.perf_counter())
        rec = flight.get_recorder()
        for i in range(300):
            flight.record("step", step=i)
        assert len(rec.events()) == rec.capacity == 256
        assert not [e for e in rec.events() if e["kind"] == "phase"]
        assert [e["name"] for e in spans.phases()] == [
            "setup/model_init", "setup/lower"]
        rec.clear()
        assert spans.phases() == []

    def test_record_phase_takes_an_earlier_start(self):
        t0 = time.perf_counter() - 1.5
        assert spans.record_phase("setup/import", t0) >= 1.5
        assert spans.phases()[-1]["t0"] == t0

    def test_package_import_is_a_phase(self):
        # the autouse fixture cleared the ring, so ask a fresh interpreter
        out = subprocess.run(
            [sys.executable, "-c",
             "import deeplearning_tpu\n"
             "from deeplearning_tpu.obs import spans\n"
             "print([(e['name'], e['seconds'] > 0) "
             "for e in spans.phases()])"],
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.strip() == "[('setup/import', True)]"

    def test_last_outlives_disable(self):
        before = spans.last()
        tracer = spans.enable()
        with span("dispatch", step=1):
            pass
        assert spans.last() is before          # still installed
        assert spans.disable() is tracer
        assert spans.last() is tracer
        names = [e["name"] for e in spans.last().events() if e["ph"] == "X"]
        assert names == ["dispatch"]
        spans.disable()                        # nothing installed: kept
        assert spans.last() is tracer
        other = spans.enable()
        spans.disable()
        assert spans.last() is other           # replaced by the next one


# ----------------------------------------------------- compile telemetry
class TestCompileTelemetry:
    def test_tracked_compile_records_flops_and_span(self):
        obs_xla.clear_compile_events()
        tracer = spans.enable()
        lowered = jax.jit(lambda x: (x @ x).sum()).lower(
            jnp.ones((16, 16), jnp.float32))
        compiled = obs_xla.tracked_compile(lowered, "unit_fn")
        assert float(compiled(jnp.ones((16, 16), jnp.float32))) == 16.0 ** 3
        events = obs_xla.compile_events()
        assert len(events) == 1
        ev = events[0]
        assert ev["fn"] == "unit_fn"
        assert ev["flops"] > 0
        assert ev["seconds"] >= 0
        stats = obs_xla.compile_stats()
        assert stats["compiles"] == 1.0
        assert stats["compile_seconds_total"] >= 0
        span_names = [e["name"] for e in tracer.events() if e["ph"] == "X"]
        assert "compile/unit_fn" in span_names

    def test_compiled_flops_routes_through_telemetry(self):
        from deeplearning_tpu.utils.profiling import compiled_flops
        obs_xla.clear_compile_events()
        flops = compiled_flops(lambda x: x @ x, jnp.ones((8, 8)))
        assert flops > 0
        assert any(e["flops"] == flops for e in obs_xla.compile_events())

    def test_hbm_snapshot_reports_live_arrays(self):
        keep = jnp.ones((128,), jnp.float32) + 0  # a live buffer
        snap = obs_xla.hbm_snapshot()
        assert snap["live_arrays"]["count"] >= 1
        assert snap["live_arrays"]["nbytes"] >= keep.nbytes
        assert isinstance(snap["devices"], list) and snap["devices"]

    @pytest.mark.parametrize("stats, peak", [
        # the v5e's client: temporaries in the reserved region (PERF.md §5)
        ({"bytes_in_use": 1_627_000_000, "peak_bytes_in_use": 1_863_000_000,
          "bytes_reserved": 7_377_000_000,
          "peak_bytes_reserved": 7_377_000_000,
          "bytes_limit": 16_900_000_000}, 9_240_000_000),
        # a client without a reserved region: the in-use peak alone
        ({"bytes_in_use": 100, "peak_bytes_in_use": 300}, 300),
        # a client with no peak at all: the largest sample
        ({"bytes_in_use": 100}, 100),
    ])
    def test_hbm_watermark_counts_the_reserved_region(self, monkeypatch,
                                                      stats, peak):
        class Dev:
            id, device_kind = 0, "stub"

            def memory_stats(self):
                return stats
        monkeypatch.setattr(jax, "devices", lambda *a, **k: [Dev()])
        snap = obs_xla.hbm_snapshot()
        entry = snap["devices"][0]
        for key, value in stats.items():
            assert entry[key] == value          # peak_bytes_reserved too
        assert entry.get("peak_bytes") == (
            peak if "peak_bytes_in_use" in stats else None)
        wm = obs_xla.HbmWatermark(interval_s=0.01)
        wm._sample()
        assert wm.watermark()["peak_bytes"] == float(peak)
        assert wm.watermark()["peak_bytes_in_use"] == float(
            stats["bytes_in_use"])

    def test_hbm_watermark_samples_from_its_thread(self):
        tracer = spans.enable()
        with obs_xla.HbmWatermark(interval_s=0.01) as wm:
            time.sleep(0.05)
        assert wm.samples >= 1
        wmk = wm.watermark()
        assert wmk["hbm_samples"] == float(wm.samples)
        hbm_events = [e for e in tracer.events()
                      if e["ph"] != "M" and e["name"] == "hbm_sample"]
        assert hbm_events
        meta = {e["tid"]: e["args"]["name"] for e in tracer.events()
                if e["ph"] == "M"}
        assert meta[hbm_events[0]["tid"]] == "obs-metrics"


# -------------------------------------------------------- flight recorder
class TestFlightRecorder:
    def test_ring_bounded_and_kind_filter(self):
        rec = FlightRecorder(capacity=4)
        for i in range(6):
            rec.record("step", step=i)
        rec.record("feed", epoch=0)
        assert rec.recorded == 7
        events = rec.events()
        assert len(events) == 4                 # bounded
        assert [e["step"] for e in rec.events("step")] == [3, 4, 5]
        assert rec.events("feed")[0]["epoch"] == 0
        assert all("time" in e and "thread" in e for e in events)

    def test_dump_without_path_is_none(self):
        rec = FlightRecorder()
        rec.record("step", step=1)
        assert rec.dump("manual") is None       # recording without arming

    def test_dump_carries_config_exception_and_nonfinite(self, tmp_path):
        rec = FlightRecorder()
        rec.record("step", step=1, loss=float("nan"),
                   arr=np.float32(2.0))
        path = str(tmp_path / "deep" / "flightrec.json")
        rec.configure(path, config={"batch": 64, "lr": 0.1})
        try:
            raise FloatingPointError("loss=nan")
        except FloatingPointError as exc:
            out = rec.dump("divergence", exception=exc)
        assert out == path
        with open(path) as f:
            doc = json.load(f)
        assert doc["reason"] == "divergence"
        assert doc["config"] == {"batch": 64, "lr": 0.1}
        assert doc["exception"]["type"] == "FloatingPointError"
        assert any("FloatingPointError" in ln
                   for ln in doc["exception"]["traceback"])
        ev = doc["events"][0]
        assert ev["loss"] == "nan"              # non-finite stringified
        assert ev["arr"] == 2.0                 # numpy scalar unboxed
        assert "live_arrays" in doc["hbm"]


# ------------------------------------------ trainer acceptance (tentpole)
def synthetic_cls(n=96, seed=0):
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 4, n).astype(np.int32)
    images = rng.normal(0, 0.1, (n, 16, 16, 1)).astype(np.float32)
    for i, l in enumerate(labels):
        images[i, :, l * 4:(l + 1) * 4, 0] += 2.0
    return images, labels


def make_trainer(train_step=None, *, epochs=1, log_every=100, n=96,
                 batch=32, **trainer_kw):
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
        epochs=epochs, log_every=log_every, **trainer_kw)


class TestTrainerTraceAcceptance:
    def test_five_step_run_trace_threads_and_compile(self, tmp_path):
        """The PR's headline artifact: a 5-step CPU run writes a
        Perfetto-loadable trace.json whose spans come from >= 3 threads
        (consumer loop, prefetch worker, HBM sampler) and carries the
        AOT compile event with FLOPs + compile-seconds args."""
        run_dir = str(tmp_path / "run")
        trainer = make_trainer(n=5 * 16, batch=16, workdir=run_dir,
                               prefetch=2, hbm_sample_s=0.01)
        assert trainer.obs_enabled            # auto: workdir set
        assert trainer.precompile() is not None
        trainer.train()
        assert not spans.enabled()            # trainer owned the tracer

        with open(os.path.join(run_dir, "trace.json")) as f:
            doc = json.load(f)
        events = doc["traceEvents"]
        xs = [e for e in events if e["ph"] == "X"]
        names = {e["name"] for e in xs}
        # the trainer's per-phase spans
        assert {"data_wait", "dispatch", "metrics_flush",
                "eval"} <= names
        assert len([e for e in xs if e["name"] == "dispatch"]) == 5
        # the prefetch worker's lanes
        assert {"feed/decode", "feed/h2d"} <= names
        # >= 3 distinct instrumented threads, with their names
        thread_names = {e["args"]["name"] for e in events
                        if e["ph"] == "M"}
        tids = {e["tid"] for e in xs}
        assert len(tids) >= 3
        assert "device-prefetch" in thread_names
        assert "obs-metrics" in thread_names
        # the AOT compile event with its telemetry args
        compile_spans = [e for e in xs
                         if e["name"] == "compile/train_step"]
        assert compile_spans
        args = compile_spans[0]["args"]
        assert args["flops"] > 0
        assert args["seconds"] >= 0
        # feed stats reached the flight ring while it ran, an event an
        # epoch, beside each loader's one tally of the route its batches took
        feed_events = flight.get_recorder().events("feed")
        epochs = [e for e in feed_events if "epoch" in e]
        assert epochs and epochs[0]["batches_fed"] == 5.0
        routes = [e for e in feed_events if "route" in e]
        assert routes and routes[0]["calls"] >= 5

    def test_obs_report_renders_the_run(self, tmp_path):
        run_dir = str(tmp_path / "run")
        trainer = make_trainer(n=3 * 16, batch=16, workdir=run_dir,
                               prefetch=2, hbm_sample_s=0.01)
        trainer.precompile()
        trainer.train()
        import obs_report
        summary = obs_report.summarize(run_dir)
        assert summary["phases"]["dispatch"]["count"] == 3
        assert summary["compiles"] and \
            summary["compiles"][0]["fn"] == "train_step"
        assert len(summary["threads"]) >= 3
        text = obs_report.render(summary)
        assert "dispatch" in text and "train_step" in text

    def test_obs_off_without_workdir_and_no_tracer_leak(self, tmp_path):
        trainer = make_trainer(n=2 * 16, batch=16)
        assert not trainer.obs_enabled
        trainer.train()
        assert not spans.enabled()
        assert flight.get_recorder().events("step") == []


class TestSigtermDumpDeferral:
    """ISSUE 13 satellite: with a graceful subscriber owning SIGTERM the
    handler only MARKS the dump pending; the trainer's step boundary
    (``flush_pending``) does the open()/json work on a normal call
    stack. Without a graceful owner the chained default terminates the
    process right after the handler, so it dumps in-handler — the last
    chance to write."""

    def test_deferred_to_flush_when_graceful_owner_present(
            self, tmp_path):
        import signal
        from deeplearning_tpu.elastic import signals
        target = tmp_path / "flightrec.json"
        flight.configure(str(target))
        flight.record("step", step=1)
        graceful = lambda s, f: None                    # noqa: E731
        assert signals.subscribe(signal.SIGTERM, graceful,
                                 graceful=True)
        try:
            flight._sigterm_dump(signal.SIGTERM, None)
            assert not target.exists()                  # deferred
            out = flight.flush_pending()
            assert out == str(target) and target.exists()
            assert json.loads(target.read_text())["reason"] == "sigterm"
            assert flight.flush_pending() is None       # one-shot
        finally:
            signals.unsubscribe(signal.SIGTERM, graceful)
            flight._PENDING.clear()

    def test_immediate_dump_without_graceful_owner(self, tmp_path):
        import signal
        target = tmp_path / "flightrec.json"
        flight.configure(str(target))
        flight.record("step", step=1)
        flight._sigterm_dump(signal.SIGTERM, None)
        assert target.exists()                          # no flush point
        assert flight.flush_pending() is None


class TestFlightDumpAcceptance:
    def test_divergence_dumps_flightrec_with_steps_and_config(
            self, tmp_path):
        """Injected bad_step divergence -> flightrec.json with reason,
        the run config, the last-K step events, and the divergence
        marker (the autopsy a diverged run used to not leave)."""
        base = make_train_step(make_loss_fn(), donate=False)

        def nan_step(state, batch, rng):
            state, metrics = base(state, batch, rng)
            bad = jnp.float32(float("nan"))
            return state, {**metrics, "loss": bad,
                           "bad_step": jnp.int32(1)}

        run_dir = str(tmp_path / "run")
        trainer = make_trainer(nan_step, n=5 * 16, batch=16,
                               workdir=run_dir, hbm_sample_s=0.01,
                               run_config={"model": "mnist_fcn",
                                           "batch": 16})
        with pytest.raises(FloatingPointError, match="non-finite"):
            trainer.train()
        path = os.path.join(run_dir, "flightrec.json")
        with open(path) as f:
            doc = json.load(f)
        assert doc["reason"] == "divergence"
        assert doc["config"] == {"model": "mnist_fcn", "batch": 16}
        assert doc["exception"]["type"] == "FloatingPointError"
        steps = [e for e in doc["events"] if e["kind"] == "step"]
        assert len(steps) == 5                 # the last-K step snapshots
        assert all(e["metrics"]["bad_step"] >= 1.0 for e in steps)
        assert any(e["kind"] == "divergence" for e in doc["events"])
        # trace.json still lands on the abort path (finally block)
        assert os.path.exists(os.path.join(run_dir, "trace.json"))

    def test_retrace_lands_in_flight_ring(self):
        trainer = make_trainer(n=2 * 16, batch=16, obs=True)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            # same treedef, new leaf shape -> one retrace event
            trainer.train_step(trainer.state,
                               {"image": jnp.zeros((16, 16, 16, 1)),
                                "label": jnp.zeros((16,), jnp.int32)},
                               trainer.rng)
            trainer.train_step(trainer.state,
                               {"image": jnp.zeros((8, 16, 16, 1)),
                                "label": jnp.zeros((8,), jnp.int32)},
                               trainer.rng)
        events = flight.get_recorder().events("retrace")
        assert len(events) == 1
        assert events[0]["n_signatures"] == 2


# --------------------------------------------------------- health surface
class TestHealthSurface:
    @pytest.fixture(scope="class")
    def engine(self):
        from deeplearning_tpu.serve import InferenceEngine
        return InferenceEngine("mnist_fcn", num_classes=10,
                               image_size=28, batch_buckets=(1, 4))

    def test_warming_engine_is_503(self):
        from deeplearning_tpu.serve import InferenceEngine, health
        cold = InferenceEngine("mnist_fcn", num_classes=10, image_size=28,
                               batch_buckets=(1, 4), precompile=False)
        code, payload = health(cold)
        assert code == 503
        assert payload["status"] == "warming"
        assert payload["engine_warm"] is False

    def test_ready_and_degraded(self, engine):
        from deeplearning_tpu.serve import MicroBatcher, health
        mb = MicroBatcher(engine, start=False)    # no dispatcher: the
        try:                                      # queue depth is ours
            code, payload = health(engine, mb)
            assert (code, payload["status"]) == (200, "ready")
            assert payload["engine_warm"] and not payload["shed"]
            assert payload["buckets"] == [1, 4]
            img = np.zeros((28, 28, 3), np.float32)
            for _ in range(engine.buckets[-1]):   # shed_threshold = 4
                mb.submit(img)
            code, payload = health(engine, mb)
            assert (code, payload["status"]) == (503, "degraded")
            assert payload["shed"] and payload["queue_depth"] >= 4
        finally:
            mb.close()

    def test_http_healthz_and_stats_routes(self, engine):
        import urllib.error
        import urllib.request
        from serve import serve_http

        from deeplearning_tpu.serve import MicroBatcher
        with MicroBatcher(engine) as mb:
            server = serve_http(mb, "classify", 28, {}, 5, 5.0, 0)
            import threading
            t = threading.Thread(target=server.serve_forever, daemon=True)
            t.start()
            try:
                base = f"http://127.0.0.1:{server.server_port}"
                with urllib.request.urlopen(base + "/healthz",
                                            timeout=5) as r:
                    hz = json.loads(r.read())
                assert hz["status"] == "ready"
                with urllib.request.urlopen(base + "/stats",
                                            timeout=5) as r:
                    stats = json.loads(r.read())
                assert stats["engine"]["warm"] is True
                assert "compiles" in stats["compile"]
                assert "live_arrays" in stats["hbm"]
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(base + "/nope", timeout=5)
                assert ei.value.code == 404
            finally:
                server.shutdown()
                server.server_close()

    def test_serve_reject_lands_in_flight_ring(self, engine):
        from deeplearning_tpu.serve import MicroBatcher, Rejected
        mb = MicroBatcher(engine, max_queue=1, start=False)
        try:
            img = np.zeros((28, 28, 3), np.float32)
            mb.submit(img)
            with pytest.raises(Rejected):
                mb.submit(img)
            events = flight.get_recorder().events("serve_reject")
            assert events and events[0]["depth"] >= 1
        finally:
            mb.close()

    def test_engine_stats_carries_warmup_telemetry(self, engine):
        stats = engine.stats()
        assert stats["warm"] is True
        assert set(stats["warmup_seconds"]) == {"1", "4"}
        assert all(v >= 0 for v in stats["warmup_seconds"].values())


# ------------------------------------------------------------- satellites
class TestRetraceGuard:
    @staticmethod
    def _guard(**kw):
        return RetraceGuard(lambda *a, **k: None, name="t", **kw)

    def test_python_scalar_weak_types_split_int_vs_float(self):
        """1 and 1.0 hash to different signatures (they produce different
        weak-typed jit cache keys), but two different ints do not."""
        g = self._guard()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            g(jnp.zeros((2,)), 1)
            g(jnp.zeros((2,)), 2)              # same type: no retrace
            assert g.retraces == 0
            g(jnp.zeros((2,)), 1.0)            # int -> float: retrace
        assert g.retraces == 1
        assert g.n_signatures == 2

    def test_max_warnings_caps_warnings_not_counting(self):
        g = self._guard(max_warnings=2)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            for n in range(1, 6):              # 5 distinct shapes
                g(jnp.zeros((n, 2)))
        assert g.retraces == 4                 # counting never stops
        assert len([w for w in caught
                    if issubclass(w.category, RuntimeWarning)]) == 2

    def test_multiscale_buckets_warn_once_each(self):
        """Deliberate shape buckets: each NEW bucket warns once; cycling
        through known buckets stays silent."""
        g = self._guard()
        shapes = [(8, 32, 32, 1), (8, 64, 64, 1), (8, 96, 96, 1)]
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            for s in shapes:
                g(jnp.zeros(s))
            for _ in range(3):                 # steady-state cycling
                for s in shapes:
                    g(jnp.zeros(s))
        assert g.retraces == 2                 # first bucket is free
        assert len([w for w in caught
                    if issubclass(w.category, RuntimeWarning)]) == 2

    def test_on_retrace_hook_fires_past_warning_cap(self):
        infos = []
        g = self._guard(max_warnings=1, on_retrace=infos.append)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            for n in range(1, 5):
                g(jnp.zeros((n,)))
        assert len(infos) == 3                 # every retrace, uncapped
        assert infos[-1] == {"name": "t", "retraces": 3,
                             "n_signatures": 4}


class TestLoggerDirCache:
    def test_new_output_dir_attaches_new_file_handler(self, tmp_path):
        name = "dltpu-test-dircache"
        d1, d2 = str(tmp_path / "run1"), str(tmp_path / "run2")
        lg1 = logging.getLogger(name)           # isolate from other tests
        from deeplearning_tpu.core.logging import create_logger
        lg1 = create_logger(name, d1, to_console=False)
        lg2 = create_logger(name, d2)           # cache hit, NEW dir
        assert lg1 is lg2                       # still one logger object
        lg2.info("hello both dirs")
        for h in lg2.handlers:
            h.flush()
        for d in (d1, d2):                      # the fix: BOTH dirs log
            files = os.listdir(d)
            assert len(files) == 1
            with open(os.path.join(d, files[0])) as f:
                assert "hello both dirs" in f.read()
        n_handlers = len(lg2.handlers)
        create_logger(name, d1)                 # seen dir: no duplicate
        assert len(lg2.handlers) == n_handlers


class TestObsReportCheck:
    def test_check_mode_passes_in_subprocess(self):
        """tools/obs_report.py --check is the tier-1-safe self-test: no
        jax import, synthetic run dir through the real obs APIs."""
        script = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "obs_report.py")
        proc = subprocess.run([sys.executable, script, "--check"],
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        assert "ok" in proc.stdout
