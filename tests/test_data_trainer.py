"""Data pipeline (transforms/mixup/mosaic/converters) + Trainer + LR finder."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_tpu.core.registry import MODELS
from deeplearning_tpu.data import ArraySource, DataLoader
from deeplearning_tpu.data import label_convert as LC
from deeplearning_tpu.data import mixup as MX
from deeplearning_tpu.data import transforms as T
from deeplearning_tpu.train import TrainState, make_eval_step, make_train_step
from deeplearning_tpu.train.classification import make_loss_fn, make_metric_fn
from deeplearning_tpu.train.lr_finder import lr_range_test
from deeplearning_tpu.train.optim import build_optimizer
from deeplearning_tpu.train.schedules import build_schedule
from deeplearning_tpu.train.trainer import Callbacks, Trainer


class TestTransforms:
    def test_resize_with_pad_scales_boxes(self):
        img = np.ones((100, 200, 3), np.float32) * 255
        boxes = np.asarray([[0, 0, 200, 100]], np.float32)
        out, scale, newb = T.resize_with_pad(img, (64, 64), boxes)
        assert out.shape == (64, 64, 3)
        assert scale == pytest.approx(64 / 200)
        np.testing.assert_allclose(newb, [[0, 0, 64, 32]], atol=0.5)
        # bottom is padding
        assert (out[40:] == 114.0).all()

    def test_normalize_and_eval_transform(self):
        imgs = np.full((2, 50, 50, 3), 128, np.float32)
        fn = T.classification_eval_transform((32, 32))
        out = fn({"image": imgs})["image"]
        assert out.shape == (2, 32, 32, 3)
        assert abs(out.mean()) < 1.0          # roughly standardized

    def test_random_flip_boxes(self):
        rng = np.random.default_rng(0)
        img = np.zeros((10, 20, 3))
        boxes = np.asarray([[2.0, 1, 6, 5]])
        img2, b2 = T.random_flip_lr(img, rng, boxes, p=1.0)
        np.testing.assert_allclose(b2, [[14, 1, 18, 5]])


class TestMixupMosaic:
    def test_mixup_soft_targets_sum_to_one(self):
        batch = {"image": jnp.ones((4, 8, 8, 3)),
                 "label": jnp.asarray([0, 1, 2, 3])}
        out = MX.mixup_cutmix(batch, jax.random.key(0), num_classes=5,
                              smoothing=0.1)
        s = np.asarray(out["label"]).sum(-1)
        np.testing.assert_allclose(s, 1.0, atol=1e-5)
        assert out["image"].shape == batch["image"].shape

    def test_mosaic4_boxes_within_canvas(self):
        rng = np.random.default_rng(0)
        imgs = [np.full((40 + i * 10, 50, 3), i * 60.0) for i in range(4)]
        boxes = [np.asarray([[5.0, 5, 30, 30]]) for _ in range(4)]
        labels = [np.asarray([i]) for i in range(4)]
        canvas, b, l, v = MX.mosaic4(imgs, boxes, labels, out_size=64,
                                     rng=rng, max_boxes=16)
        assert canvas.shape == (64, 64, 3)
        assert b.shape == (16, 4) and v.sum() >= 1
        bb = b[v]
        assert (bb >= 0).all() and (bb <= 64).all()


class TestLabelConvert:
    def _rec(self):
        return {"filename": "a.jpg", "width": 100, "height": 80,
                "boxes": np.asarray([[10.0, 10, 50, 40],
                                     [60, 20, 90, 70]], np.float32),
                "names": ["cat", "dog"],
                "difficult": np.asarray([False, False])}

    def test_voc_xml_roundtrip(self, tmp_path):
        p = str(tmp_path / "a.xml")
        LC.write_voc_xml(self._rec(), p)
        back = LC.parse_voc_xml(p)
        np.testing.assert_allclose(back["boxes"], self._rec()["boxes"])
        assert back["names"] == ["cat", "dog"]

    def test_coco_roundtrip(self):
        coco = LC.records_to_coco([self._rec()], ["cat", "dog"])
        assert len(coco["annotations"]) == 2
        assert coco["annotations"][0]["bbox"] == [10.0, 10, 40, 30]
        back = LC.coco_to_records(coco)[0]
        np.testing.assert_allclose(back["boxes"], self._rec()["boxes"])

    def test_yolo_roundtrip(self):
        txt = LC.record_to_yolo(self._rec(), ["cat", "dog"])
        assert txt.splitlines()[0].startswith("0 ")
        back = LC.yolo_to_record(txt, 100, 80, ["cat", "dog"])
        np.testing.assert_allclose(back["boxes"], self._rec()["boxes"],
                                   atol=0.01)

    def test_records_to_arrays_padding(self):
        arrs = LC.records_to_arrays([self._rec()], ["cat", "dog"],
                                    max_boxes=5)
        assert arrs["boxes"].shape == (1, 5, 4)
        assert arrs["valid"][0].sum() == 2
        assert list(arrs["labels"][0][:2]) == [0, 1]


def synthetic_cls(n=96, seed=0):
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 4, n).astype(np.int32)
    images = rng.normal(0, 0.1, (n, 16, 16, 1)).astype(np.float32)
    for i, l in enumerate(labels):
        images[i, :, l * 4:(l + 1) * 4, 0] += 2.0
    return images, labels


class TestTrainer:
    def _make(self, workdir=None, epochs=2):
        images, labels = synthetic_cls()
        model = MODELS.build("mnist_fcn", num_classes=4, dtype=jnp.float32)
        params = model.init(jax.random.key(0),
                            jnp.zeros((1, 16, 16, 1)))["params"]
        tx = build_optimizer(
            "sgd", build_schedule("constant", base_lr=0.1), params=params)
        state = TrainState.create(apply_fn=model.apply, params=params,
                                  tx=tx)
        loader = DataLoader(ArraySource(image=images, label=labels),
                            global_batch=32, seed=0)
        eval_loader = DataLoader(ArraySource(image=images, label=labels),
                                 global_batch=32, shuffle=False)
        return Trainer(
            state=state,
            train_step=make_train_step(make_loss_fn(), donate=False),
            train_loader=loader,
            eval_step=make_eval_step(make_metric_fn(ks=(1,))),
            eval_loader=eval_loader,
            epochs=epochs, workdir=workdir, best_metric="top1",
            log_every=100)

    def test_trains_and_evaluates_with_hooks(self, tmp_path):
        trainer = self._make(str(tmp_path / "run"))
        events = []
        for ev in ("before_train", "before_epoch", "after_epoch",
                   "on_evaluate", "after_train"):
            trainer.callbacks.register(
                ev, lambda t, _e=ev, **kw: events.append(_e))
        trainer.train()
        assert events[0] == "before_train" and events[-1] == "after_train"
        assert events.count("before_epoch") == 2
        res = trainer.evaluate()
        assert res["top1"] > 0.9
        # checkpoint + best written
        assert os.path.isdir(str(tmp_path / "run" / "ckpt" / "best"))
        trainer.ckpt.close()

    def test_auto_resume_continues(self, tmp_path):
        wd = str(tmp_path / "run")
        t1 = self._make(wd, epochs=1)
        t1.train()
        step_after = int(t1.state.step)
        t1.ckpt.close()
        t2 = self._make(wd, epochs=2)
        t2.train()                      # resumes from epoch 1
        assert int(t2.state.step) == step_after * 2
        t2.ckpt.close()


class TestLrFinder:
    def test_suggests_reasonable_lr(self):
        images, labels = synthetic_cls(128)
        model = MODELS.build("mnist_fcn", num_classes=4, dtype=jnp.float32)
        params0 = model.init(jax.random.key(0),
                             jnp.zeros((1, 16, 16, 1)))["params"]

        def make_state(schedule):
            import optax
            return TrainState.create(
                apply_fn=model.apply, params=params0,
                tx=optax.sgd(schedule))

        batches = [{"image": jnp.asarray(images[i:i + 16]),
                    "label": jnp.asarray(labels[i:i + 16])}
                   for i in range(0, 128, 16)]
        res = lr_range_test(
            make_state, lambda s: make_train_step(make_loss_fn(),
                                                  donate=False),
            batches * 3, min_lr=1e-5, max_lr=10.0)
        assert 1e-5 < res["suggestion"] < 10.0
        assert len(res["lrs"]) == len(res["losses"])


def test_parallel_loader_matches_serial():
    """num_workers>0 must yield the same batches in the same order as
    the serial path (decode runs on a pool, assembly stays ordered)."""
    from deeplearning_tpu.data.loader import DataLoader, MapSource

    def fetch(i):
        return {"x": np.full((3,), i, np.float32),
                "label": np.asarray(i, np.int32)}

    src = MapSource(37, fetch)
    serial = DataLoader(src, 8, shuffle=True, seed=3)
    pooled = DataLoader(src, 8, shuffle=True, seed=3, num_workers=4,
                        lookahead=3)
    for a, b in zip(serial, pooled):
        np.testing.assert_array_equal(a["x"], b["x"])
        np.testing.assert_array_equal(a["label"], b["label"])
    assert len(list(iter(pooled))) == len(serial)


class TestLoggerHub:
    """Pluggable logger backends (yolov5 utils/loggers/__init__.py:17-27
    csv/TensorBoard/W&B trio; the W&B slot is the offline JSONL sink)."""

    def test_backends_write(self, tmp_path):
        import json

        from deeplearning_tpu.core.logging import LoggerHub
        hub = LoggerHub(str(tmp_path), ("csv", "jsonl"))
        hub.scalars({"train/loss": 1.5, "train/acc": 0.5}, step=1)
        hub.scalars({"train/loss": 1.0, "train/acc": 0.7}, step=2)
        hub.summary({"top1": 0.9})
        hub.close()
        csv_lines = (tmp_path / "results.csv").read_text().splitlines()
        assert csv_lines[0].startswith("step,")
        assert len(csv_lines) == 3
        recs = [json.loads(l) for l in
                (tmp_path / "metrics.jsonl").read_text().splitlines()]
        assert recs[0]["step"] == 1 and recs[1]["train/acc"] == 0.7
        assert recs[-1]["summary"] is True and recs[-1]["top1"] == 0.9

    def test_unknown_backend_fails_loudly(self, tmp_path):
        import pytest

        from deeplearning_tpu.core.logging import LoggerHub
        with pytest.raises(KeyError, match="wandb_online"):
            LoggerHub(str(tmp_path), ("csv", "wandb_online"))
