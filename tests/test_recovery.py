"""Self-healing runs (PR 7): divergence rollback-and-skip, bad-batch
quarantine, hardened checkpoint I/O, and the serving wedge surface."""

import json
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_tpu.core.checkpoint import CheckpointManager
from deeplearning_tpu.core.registry import MODELS
from deeplearning_tpu.data import (ArraySource, DataLoader, PoisonedData,
                                   QuarantineLog, quarantinable)
from deeplearning_tpu.elastic import faults
from deeplearning_tpu.elastic.preempt import agree_preempt_step
from deeplearning_tpu.train import (RecoveryExhausted, RecoveryManager,
                                    RecoveryPolicy, TrainState,
                                    make_eval_step, make_train_step)
from deeplearning_tpu.train import recovery as recovery_mod
from deeplearning_tpu.train.classification import make_loss_fn, make_metric_fn
from deeplearning_tpu.train.optim import build_optimizer
from deeplearning_tpu.train.schedules import build_schedule
from deeplearning_tpu.train.trainer import Trainer

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))


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


def make_trainer(train_step=None, *, epochs=1, log_every=100, n=96,
                 metrics_lag=None, batch=32, **trainer_kw):
    images, labels = synthetic_cls(n)
    loader = DataLoader(ArraySource(image=images, label=labels),
                        global_batch=batch, seed=0)
    eval_loader = DataLoader(ArraySource(image=images, label=labels),
                             global_batch=batch, shuffle=False)
    return Trainer(
        state=make_state(),
        train_step=train_step or make_train_step(make_loss_fn(),
                                                 donate=False),
        train_loader=loader,
        eval_step=make_eval_step(make_metric_fn(ks=(1,))),
        eval_loader=eval_loader,
        epochs=epochs, log_every=log_every, metrics_lag=metrics_lag,
        **trainer_kw)


class _FlakySource:
    """ArraySource-alike whose __getitem__ raises on chosen indices —
    the corrupt-JPEG stand-in the quarantine path must survive."""

    def __init__(self, n=64, bad=(), exc=ValueError):
        self.images, self.labels = synthetic_cls(n)
        self.bad = set(bad)
        self.exc = exc

    def __len__(self):
        return len(self.labels)

    def __getitem__(self, idx):
        idx_arr = np.atleast_1d(np.asarray(idx))
        hit = self.bad.intersection(int(i) for i in idx_arr)
        if hit:
            raise self.exc(f"decode failed for sample {sorted(hit)}")
        return {"image": self.images[idx], "label": self.labels[idx]}


# --------------------------------------------------------- RecoveryManager
class TestRecoveryManager:
    def tree(self, v=0.0):
        return {"w": jnp.full((3,), float(v))}

    def test_promotion_requires_strictly_newer_finite_entry(self):
        mgr = RecoveryManager(RecoveryPolicy(anchor_every=2))
        mgr.seed(0, self.tree(0))
        mgr.maybe_snapshot(2, self.tree(2))
        assert mgr.anchor_step == 0           # pending, not promoted
        mgr.mark_verified(2)                  # entry AT 2 vouches for 1,
        assert mgr.anchor_step == 0           # not for state 2 itself
        mgr.mark_verified(3)
        assert mgr.anchor_step == 2

    def test_snapshot_cadence_is_anchor_every(self):
        mgr = RecoveryManager(RecoveryPolicy(anchor_every=5))
        mgr.seed(0, self.tree())
        for step in range(1, 12):
            mgr.maybe_snapshot(step, self.tree(step))
        assert [s for s, _ in mgr._pending] == [5, 10]

    def test_rollback_returns_anchor_copy_and_skips_window(self):
        mgr = RecoveryManager(RecoveryPolicy(anchor_every=2,
                                             cooldown_steps=3,
                                             lr_decay=0.25))
        mgr.seed(0, self.tree(0))
        mgr.maybe_snapshot(2, self.tree(2))
        mgr.mark_verified(3)
        step, state = mgr.on_divergence(4)
        assert step == 2
        assert float(state["w"][0]) == 2.0
        assert mgr.skipped == [(2, 4)]
        # cooldown covers [anchor, anchor + cooldown_steps)
        assert mgr.cooldown_scale(3) == 0.25
        assert mgr.cooldown_scale(5) is None
        # the anchor survives: a second divergence in the same window
        # rolls back to the SAME state even if the first copy was mutated
        state["w"] = state["w"] * 0 - 1
        _, again = mgr.on_divergence(4)
        assert float(again["w"][0]) == 2.0
        assert mgr.rollbacks == 2

    def test_budget_exhaustion_raises(self):
        mgr = RecoveryManager(RecoveryPolicy(anchor_every=1,
                                             max_recoveries=2))
        mgr.seed(0, self.tree())
        mgr.on_divergence(1)
        mgr.on_divergence(2)
        with pytest.raises(RecoveryExhausted, match="already spent"):
            mgr.on_divergence(3)

    def test_windowed_budget_forgets_old_rollbacks(self):
        mgr = RecoveryManager(RecoveryPolicy(anchor_every=1,
                                             max_recoveries=1,
                                             budget_steps=10))
        mgr.seed(0, self.tree())
        mgr.on_divergence(1)
        with pytest.raises(RecoveryExhausted):
            mgr.on_divergence(5)              # inside the window
        assert mgr.on_divergence(20)[0] == 0  # step 1 aged out

    def test_no_anchor_raises(self):
        mgr = RecoveryManager(RecoveryPolicy())
        with pytest.raises(RecoveryExhausted, match="no verified anchor"):
            mgr.on_divergence(3)

    def test_abort_mode_policy_rejected_values(self):
        with pytest.raises(ValueError, match="rollback|abort"):
            RecoveryPolicy(mode="retry")

    def test_damp_update_is_leafwise_lerp(self):
        old = {"w": jnp.zeros((4,))}
        new = {"w": jnp.full((4,), 8.0)}
        out = recovery_mod.damp_update(old, new, 0.25)
        np.testing.assert_allclose(np.asarray(out["w"]), 2.0)


# ----------------------------------------------------------- fault grammar
class TestSelfHealingFaults:
    def test_parse_new_kinds(self):
        specs = faults.parse_faults(
            "nan@step:4;bad_sample@step:9;ckpt_corrupt@checkpoint:2")
        assert [(s.kind, s.site, s.at_step) for s in specs] == [
            ("nan", "step", 4), ("bad_sample", "step", 9),
            ("ckpt_corrupt", "checkpoint", 2)]

    def test_consumed_kinds_never_fire_but_consume_once(self, monkeypatch):
        monkeypatch.setenv(faults.ENV_VAR, "nan@step:3")
        monkeypatch.delenv(faults.ATTEMPT_VAR, raising=False)
        faults.reset()
        try:
            faults.maybe_fire("step", step=10)         # no delivery
            assert not faults.consume("nan", "step", step=2)  # below floor
            assert faults.consume("nan", "step", step=3)
            assert not faults.consume("nan", "step", step=4)  # once only
        finally:
            faults.reset()


# -------------------------------------------------------------- quarantine
class TestQuarantine:
    def test_serial_loader_quarantines_and_fills_batch(self, tmp_path):
        qlog = QuarantineLog(str(tmp_path / "quarantine.jsonl"))
        src = _FlakySource(n=64, bad=(3, 17))
        loader = DataLoader(src, global_batch=8, shuffle=False,
                            quarantine=qlog)
        batches = list(loader)
        assert len(batches) == 8
        for b in batches:                      # batches stay full-shape
            assert b["image"].shape[0] == 8
        assert qlog.quarantined == 2
        rows = [json.loads(line) for line in
                open(tmp_path / "quarantine.jsonl")]
        assert sorted(r["index"] for r in rows) == [3, 17]
        assert all("decode failed" in r["error"] for r in rows)

    def test_parallel_loader_quarantines(self, tmp_path):
        qlog = QuarantineLog(str(tmp_path / "q.jsonl"))
        src = _FlakySource(n=64, bad=(5,))
        loader = DataLoader(src, global_batch=8, shuffle=False,
                            num_workers=2, quarantine=qlog)
        batches = list(loader)
        assert len(batches) == 8
        assert qlog.quarantined == 1

    def test_escalation_raises_poisoned_data(self, tmp_path):
        qlog = QuarantineLog(str(tmp_path / "q.jsonl"),
                             max_poisoned_frac=0.05, min_samples=16)
        src = _FlakySource(n=64, bad=set(range(0, 64, 4)))   # 25% bad
        loader = DataLoader(src, global_batch=8, shuffle=False,
                            quarantine=qlog)
        with pytest.raises(PoisonedData, match="poisoned"):
            list(loader)

    def test_bad_sample_fault_routes_through_quarantine(self, tmp_path,
                                                        monkeypatch):
        monkeypatch.setenv(faults.ENV_VAR, "bad_sample@step:5")
        faults.reset()
        try:
            qlog = QuarantineLog(str(tmp_path / "q.jsonl"))
            src = _FlakySource(n=32, bad=())
            loader = DataLoader(src, global_batch=8, shuffle=False,
                                quarantine=qlog)
            batches = list(loader)
        finally:
            faults.reset()
        assert len(batches) == 4
        assert qlog.quarantined == 1
        row = json.loads(open(tmp_path / "q.jsonl").readline())
        assert "InjectedBadSample" in row["error"]

    def test_parallel_nonquarantinable_reraises_with_traceback(self):
        src = _FlakySource(n=32, bad=(9,), exc=MemoryError)
        loader = DataLoader(src, global_batch=8, shuffle=False,
                            num_workers=2,
                            quarantine=QuarantineLog(os.devnull))
        with pytest.raises(MemoryError) as ei:
            list(loader)
        # original worker traceback survives the thread hop
        assert any("_fetch_one" in str(f) for f in ei.traceback)

    def test_serial_no_quarantine_keeps_seed_behavior(self):
        src = _FlakySource(n=32, bad=(9,))
        loader = DataLoader(src, global_batch=8, shuffle=False)
        with pytest.raises(ValueError, match="decode failed"):
            list(loader)

    def test_quarantinable_predicate(self):
        assert quarantinable(ValueError("x"))
        assert not quarantinable(MemoryError())
        assert not quarantinable(PoisonedData("x"))
        assert not quarantinable(KeyboardInterrupt())

    def test_reseed_changes_order(self):
        images, labels = synthetic_cls(32)
        loader = DataLoader(ArraySource(image=images, label=labels),
                            global_batch=8, seed=0)
        first = np.concatenate([b["label"] for b in loader])
        loader.reseed(1)
        second = np.concatenate([b["label"] for b in loader])
        assert sorted(first.tolist()) == sorted(second.tolist())
        assert first.tolist() != second.tolist()


# ------------------------------------------------------ checkpoint hardening
class TestCheckpointHardening:
    def save_steps(self, tmp_path, steps=(1, 2, 3)):
        state = make_state()
        ckpt = CheckpointManager(str(tmp_path / "ckpt"), max_to_keep=8)
        for s in steps:
            state = state.replace(step=jnp.asarray(s, jnp.int32))
            ckpt.save(s, state)
        ckpt.wait_until_finished()
        return ckpt, state

    def test_checksum_sidecar_and_verify(self, tmp_path):
        ckpt, _ = self.save_steps(tmp_path)
        sidecar = tmp_path / "ckpt" / "checksums.json"
        assert sidecar.exists()
        table = json.loads(sidecar.read_text())
        assert set(table) == {"1", "2", "3"}
        assert all(ckpt.verify_step(s) for s in (1, 2, 3))
        faults.corrupt_checkpoint(str(tmp_path / "ckpt"), 3)
        assert not ckpt.verify_step(3)
        assert ckpt.verify_step(2)

    def test_restore_falls_back_to_newest_intact_step(self, tmp_path):
        ckpt, state = self.save_steps(tmp_path)
        faults.corrupt_checkpoint(str(tmp_path / "ckpt"), 3)
        restored, step = ckpt.restore_verified(make_state(seed=1))
        assert step == 2
        assert int(restored.step) == 2
        # bitwise parity with a direct restore of the intact step
        direct = ckpt.restore(make_state(seed=1), step=2)
        for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(direct)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # the corrupt dir is quarantined out of Orbax's step scan
        assert (tmp_path / "ckpt" / "corrupt-3").exists()
        assert not (tmp_path / "ckpt" / "3").exists()
        assert ckpt.latest_step() == 2

    def test_unverifiable_step_is_trusted(self, tmp_path):
        # steps without a sidecar entry (pre-PR-7 checkpoints) restore
        ckpt, _ = self.save_steps(tmp_path, steps=(1,))
        os.remove(tmp_path / "ckpt" / "checksums.json")
        assert ckpt.verify_step(1)
        restored, step = ckpt.restore_verified(make_state(seed=1))
        assert step == 1 and int(restored.step) == 1

    def test_all_steps_corrupt_returns_none(self, tmp_path):
        ckpt, _ = self.save_steps(tmp_path, steps=(1, 2))
        faults.corrupt_checkpoint(str(tmp_path / "ckpt"), 1)
        faults.corrupt_checkpoint(str(tmp_path / "ckpt"), 2)
        restored, step = ckpt.restore_verified(make_state(seed=1))
        assert restored is None and step == 0

    def test_auto_resume_routes_through_verification(self, tmp_path):
        ckpt, _ = self.save_steps(tmp_path)
        faults.corrupt_checkpoint(str(tmp_path / "ckpt"), 3)
        _, step = ckpt.auto_resume(make_state(seed=1))
        assert step == 2


# --------------------------------------------------------- trainer e2e
class TestTrainerSelfHealing:
    def test_nan_fault_rolls_back_and_completes(self, tmp_path,
                                                monkeypatch):
        monkeypatch.setenv(faults.ENV_VAR, "nan@step:3")
        monkeypatch.delenv(faults.ATTEMPT_VAR, raising=False)
        faults.reset()
        try:
            trainer = make_trainer(
                epochs=2, log_every=2, metrics_lag=1, n=96, batch=32,
                workdir=str(tmp_path), obs=True,
                recovery=RecoveryPolicy(anchor_every=2, cooldown_steps=2))
            trainer.train()                    # must NOT raise
        finally:
            faults.reset()
        assert trainer._recovery.rollbacks >= 1
        rec = json.loads((tmp_path / "flightrec.json").read_text())
        kinds = {e["kind"] for e in rec["events"]}
        assert {"fault_injected", "divergence", "recovery",
                "recovery_summary"} <= kinds
        assert rec["reason"] == "recovered"
        recov = next(e for e in rec["events"] if e["kind"] == "recovery")
        assert recov["anchor_step"] < recov["step"]

    def test_abort_mode_keeps_seed_behavior(self, monkeypatch):
        monkeypatch.setenv(faults.ENV_VAR, "nan@step:3")
        faults.reset()
        try:
            trainer = make_trainer(epochs=2, log_every=2, metrics_lag=1,
                                   n=96, batch=32, recovery=None)
            with pytest.raises(FloatingPointError, match="non-finite"):
                trainer.train()
        finally:
            faults.reset()

    def test_exhausted_budget_falls_through_to_abort(self, monkeypatch):
        # every step poisons -> rollback budget spends out -> seed abort
        monkeypatch.setenv(faults.ENV_VAR,
                           "nan@step:2;nan@step:2;nan@step:2")
        faults.reset()
        try:
            trainer = make_trainer(
                epochs=4, log_every=1, metrics_lag=1, n=32, batch=32,
                recovery=RecoveryPolicy(anchor_every=1, max_recoveries=1,
                                        cooldown_steps=0))
            with pytest.raises(FloatingPointError, match="non-finite"):
                trainer.train()
        finally:
            faults.reset()
        assert trainer._recovery.rollbacks == 1

    def test_ckpt_corrupt_fault_resumes_from_intact_step(self, tmp_path,
                                                         monkeypatch):
        monkeypatch.setenv(faults.ENV_VAR, "ckpt_corrupt@checkpoint:4")
        faults.reset()
        try:
            trainer = make_trainer(epochs=2, n=96, batch=32,
                                   workdir=str(tmp_path))
            trainer.train()                    # saves at steps 3 and 6;
        finally:                               # the step-6 dir is garbled
            faults.reset()
        ckpt = CheckpointManager(str(tmp_path / "ckpt"))
        assert not ckpt.verify_step(6)
        restored, step = ckpt.auto_resume(make_state(seed=1))
        assert step == 3
        direct = ckpt.restore(make_state(seed=1), step=3)
        for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(direct)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------- multi-host agreement
class TestPreemptAgreement:
    def test_single_host_is_identity(self):
        assert agree_preempt_step(7) == 7


# --------------------------------------------------------- serve wedge/beat
class TestServeSupervision:
    @pytest.fixture(scope="class")
    def engine(self):
        from deeplearning_tpu.serve import InferenceEngine
        return InferenceEngine("mnist_fcn", num_classes=10,
                               image_size=28, batch_buckets=(1, 4))

    def test_dispatch_touches_heartbeat(self, engine):
        from deeplearning_tpu.elastic import heartbeat as hb
        from deeplearning_tpu.serve import MicroBatcher
        beat = hb.Heartbeat()
        with MicroBatcher(engine, heartbeat=beat) as mb:
            h = mb.submit(np.zeros((28, 28, 3), np.float32))
            h.result(timeout=10.0)
            assert mb.dispatched >= 1
        assert beat.phase == "dispatch"
        assert beat.step >= 1 and beat.activity >= 1

    def test_idle_server_never_wedges(self, engine):
        from deeplearning_tpu.serve import MicroBatcher
        from deeplearning_tpu.serve.health import DispatchWatch, health
        with MicroBatcher(engine) as mb:
            watch = DispatchWatch(mb, deadline_s=0.0)
            for _ in range(3):
                assert watch.verdict() != "wedged"
            code, payload = health(engine, mb, wedge=watch)
            assert code == 200 and payload["wedged"] is False

    def test_frozen_dispatch_reports_wedged_over_http(self, engine):
        import urllib.request
        import urllib.error
        from serve import serve_http

        from deeplearning_tpu.serve import MicroBatcher
        mb = MicroBatcher(engine)
        server = None
        try:
            # freeze the dispatch thread, then queue work: the classic
            # wedge signature (connections answered, counter frozen)
            mb._stop.set()
            mb._thread.join(5.0)
            assert not mb._thread.is_alive()
            mb.submit(np.zeros((28, 28, 3), np.float32))
            server = serve_http(mb, "classify", 28, {}, 5, 5.0, 0, 0.0)
            threading.Thread(target=server.serve_forever,
                             daemon=True).start()
            base = f"http://127.0.0.1:{server.server_port}"
            payload = None
            for _ in range(4):      # detector needs repeat observations
                try:
                    with urllib.request.urlopen(base + "/healthz",
                                                timeout=5) as r:
                        payload = json.loads(r.read())
                except urllib.error.HTTPError as e:
                    payload = json.loads(e.read())
                    if payload["status"] == "wedged":
                        break
            assert payload["status"] == "wedged"
            assert payload["wedged"] is True
            assert payload["stalled_s"] >= 0.0
            assert payload["queue_depth"] >= 1
        finally:
            if server is not None:
                server.shutdown()
                server.server_close()
            mb.close()
