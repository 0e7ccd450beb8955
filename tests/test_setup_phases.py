"""The set-up's phases and the feed's lane as a run leaves them behind:
``tools/train.py::build_trainer`` tiles itself with ``setup/*`` phases, the
window's spans stay readable through ``spans.last()``, one batch number joins
``feed/*`` to ``data_wait`` to ``dispatch``, the benchmark's two readers turn
both into numbers, and the public names the train driver is to move onto."""

import os
import statistics
import sys
import threading
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

from benchmarks.readers import phase_s, span_ms
from deeplearning_tpu.core.config import config_cli
from deeplearning_tpu.obs import flight, spans
from deeplearning_tpu.obs.xla import compile_events

SETUP_PHASES = ("setup/mesh", "setup/data", "setup/model_init", "setup/state",
                "setup/step_build", "setup/lower", "setup/posture")
TINY = ["model.name=mnist_fcn", "data.n_train=128", "data.global_batch=32",
        "train.epochs=2", "train.seed=3"]


@pytest.fixture(autouse=True)
def _clean_obs_globals():
    spans.disable()
    flight.get_recorder().clear()
    yield
    spans.disable()
    flight.get_recorder().clear()


def build(*extra):
    import train as train_cli
    return train_cli.build_trainer(
        config_cli(train_cli.Config(), TINY + list(extra)))


@pytest.fixture
def feed_threads():
    """The live ``device-prefetch`` workers started since the test began:
    its own trainers'. A worker that a test of another file left parked on
    its full queue is a daemon thread that lives as long as the process,
    and which files share a process is the test runner's choice."""
    before = set(threading.enumerate())
    return lambda: [t for t in threading.enumerate()
                    if t.name == "device-prefetch" and t.is_alive()
                    and t not in before]


def xs(tracer, name):
    return [e for e in tracer.events()
            if e["ph"] == "X" and e["name"] == name]


class TestBuildTrainerPhases:
    def test_every_setup_phase_once_and_in_order(self):
        n_compiles = len(compile_events())
        trainer = build()
        trainer.close_feed()
        trainer.train_loader.reseed(0)       # stops the started pipeline
        phases = spans.phases()
        assert [e["name"] for e in phases] == list(SETUP_PHASES)
        assert all(e["seconds"] > 0 for e in phases)
        # they tile: each begins where the one before it ended, but for
        # the compile, which sits between the lowering and the posture
        ends = [e["t0"] + e["seconds"] for e in phases]
        for before, after in zip(ends[:5], phases[1:6]):
            assert 0 <= after["t0"] - before < 0.25
        (compiled,) = compile_events()[n_compiles:]
        assert compiled["fn"] == "train_step"
        gap = phases[6]["t0"] - ends[5]       # lower's end -> posture
        assert compiled["seconds"] <= gap < compiled["seconds"] + 0.25
        assert trainer.precompile_seconds >= (phases[5]["seconds"]
                                              + compiled["seconds"])

    def test_phase_reader_reads_the_last_of_a_name(self):
        assert phase_s.read(None, {"name": "setup/model_init"}) is None
        build().train_loader.reseed(0)
        first = phase_s.read(None, {"name": "setup/model_init"})
        assert first == spans.phases()[2]["seconds"] > 0
        with spans.phase("setup/model_init"):
            pass
        assert phase_s.read(None, {"name": "setup/model_init"}) == \
            spans.phases()[-1]["seconds"] < first
        assert phase_s.read(None, {"name": "setup/no_such"}) is None

    def test_phase_reader_on_a_program_without_phases(self, monkeypatch):
        with spans.phase("setup/data"):
            pass
        monkeypatch.delattr(spans, "phases")
        assert phase_s.read(None, {"name": "setup/data"}) is None


class TestWindowTimeline:
    def test_batch_number_follows_decode_to_dispatch(self):
        trainer = build("train.epochs=2")
        tracer = spans.enable()       # as the driver does, after set-up
        tracer.clear()
        trainer.train()
        assert spans.disable() is tracer
        del trainer                   # the reader's view: all that is left
        ring = spans.last()
        waits = [e for e in xs(ring, "data_wait") if "batch" in e["args"]]
        steps = {e["args"]["step"]: e for e in xs(ring, "dispatch")}
        assert [e["args"]["step"] for e in waits] == list(range(8))
        # 2 epochs x 4 batches, numbered by the worker in feeding order
        assert [e["args"]["batch"] for e in waits] == list(range(8))
        h2d = {e["args"]["batch"]: e for e in xs(ring, "feed/h2d")}
        for wait in waits:
            batch, step = wait["args"]["batch"], wait["args"]["step"]
            assert step in steps
            if batch in h2d:          # fed before the ring came on: absent
                fed = h2d[batch]
                # how long the batch sat in the queue: never negative
                assert wait["ts"] + wait["dur"] >= fed["ts"] + fed["dur"] - 1
                assert steps[step]["ts"] >= wait["ts"]
        assert len(h2d) >= 4          # the second epoch's at the least
        # the end-of-epoch wait that found the feed exhausted has no batch
        assert len(xs(ring, "data_wait")) == len(waits) + 2

    def test_span_reader_reads_the_window_after_disable(self):
        params = {"names": ["feed/decode", "feed/h2d"], "stat": "median"}
        trainer = build("train.epochs=3")
        tracer = spans.enable()
        trainer.train()
        spans.disable()
        value = span_ms.read(None, params)
        sums = span_ms.batch_sums_ms(tracer.events(), params["names"])
        assert len(sums) >= 8      # 3 epochs x 4, less what precompile fed
        assert value == pytest.approx(statistics.median(sums)) and value > 0
        h2d = span_ms.read(None, {"names": ["feed/h2d"], "stat": "median"})
        assert 0 < h2d <= value


def _event(name, batch, dur_us, ph="X"):
    ev = {"ph": ph, "name": name, "ts": 0.0, "dur": dur_us}
    if batch is not None:
        ev["args"] = {"batch": batch}
    return ev


class TestSpanReader:
    EVENTS = [
        {"ph": "M", "name": "thread_name", "args": {"name": "worker"}},
        _event("feed/h2d", 6, 50_000),          # decode fell before the window
        _event("feed/decode", 7, 2_000), _event("feed/h2d", 7, 100_000),
        _event("feed/put_wait", 7, 40_000),     # not asked for: not summed
        _event("feed/decode", 8, 4_000), _event("feed/h2d", 8, 90_000),
        _event("feed/decode", 9, 3_000), _event("feed/h2d", 9, 200_000),
        _event("feed/decode", 10, 3_000),       # h2d fell after the window
        _event("data_wait", 8, 70_000),         # another span of batch 8
        _event("feed/h2d", None, 1e9),          # a program without numbers
    ]

    def _last(self, monkeypatch, events):
        ring = types.SimpleNamespace(events=lambda: events)
        monkeypatch.setattr(spans, "last", lambda: ring)

    @pytest.mark.parametrize("params, value", [
        ({"names": ["feed/decode", "feed/h2d"], "stat": "median"}, 102.0),
        ({"names": ["feed/decode", "feed/h2d"], "stat": "mean"},
         (102.0 + 94.0 + 203.0) / 3),
        ({"names": ["feed/h2d"], "stat": "median"}, 95.0),   # 50 90 100 200
        ({"names": ["feed/put_wait"], "stat": "median"}, 40.0),
    ])
    def test_only_complete_batches_count(self, monkeypatch, params, value):
        self._last(monkeypatch, self.EVENTS)
        assert span_ms.read(None, params) == pytest.approx(value)

    def test_batch_sums_keep_batch_order(self):
        assert span_ms.batch_sums_ms(
            self.EVENTS, ["feed/decode", "feed/h2d"]) == [102.0, 94.0, 203.0]

    @pytest.mark.parametrize("case", ["never_on", "empty", "no_pair",
                                      "old_program"])
    def test_nothing_recorded_reads_none_not_zero(self, monkeypatch, case):
        params = {"names": ["feed/decode", "feed/h2d"], "stat": "median"}
        if case == "never_on":
            monkeypatch.setattr(spans, "last", lambda: None)
        elif case == "empty":
            self._last(monkeypatch, [])
        elif case == "no_pair":
            self._last(monkeypatch, [_event("feed/h2d", 1, 5.0),
                                     _event("feed/decode", 2, 5.0)])
        else:
            monkeypatch.delattr(spans, "last")
        assert span_ms.read(None, params) is None


class TestPublicNamesForTheDriver:
    def test_compiled_step_text_is_the_aot_steps(self):
        trainer = build("train.precompile=false")
        assert trainer.compiled_step_text() is None
        assert trainer.precompile() > 0
        text = trainer.compiled_step_text()
        assert text == trainer._aot_step.as_text() and "HloModule" in text
        trainer.train_loader.reseed(0)

    def test_close_feed_stops_the_worker(self, feed_threads):
        trainer = build()
        trainer.close_feed()          # nothing live yet: a no-op
        trainer.train_loader.infinite = True
        trainer.callbacks.register(
            "after_iter", lambda tr, metrics: tr.request_stop())
        trainer.train()               # leaves the endless epoch part-way
        assert not feed_threads()
        # by hand, as the driver does after it has left train() by raising
        trainer._batches = iter(trainer.train_loader)
        next(trainer._batches)
        assert feed_threads()
        trainer.close_feed()
        assert not feed_threads()
        with pytest.raises(StopIteration):
            next(trainer._batches)

    def test_request_stop_returns_the_state_at_a_step_boundary(self,
                                                               feed_threads):
        trainer = build("train.epochs=50")
        trainer.train_loader.infinite = True     # one endless epoch
        fired = {"after_train": 0, "after_epoch": 0, "evals": 0, "steps": 0}

        def after_iter(tr, metrics):
            fired["steps"] += 1
            if fired["steps"] == 5:
                tr.request_stop()
        trainer.callbacks.register("after_iter", after_iter)
        for hook in ("after_train", "after_epoch"):
            trainer.callbacks.register(
                hook, lambda tr, h=hook: fired.__setitem__(h, fired[h] + 1))
        trainer.callbacks.register(
            "on_evaluate", lambda tr, results: fired.__setitem__("evals", 1))
        state = trainer.train()
        assert state is trainer.state and int(state.step) == 5
        assert fired == {"after_train": 1, "after_epoch": 0, "evals": 0,
                         "steps": 5}
        assert trainer.deferred.pending == 0     # the tail was drained
        assert not feed_threads()
        # the flag is spent: a second train() runs on until asked again
        fired["steps"] = 3
        assert int(trainer.train().step) == 7
