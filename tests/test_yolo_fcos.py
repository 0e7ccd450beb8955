"""FCOS + YOLOX: target generation, SimOTA, losses, postprocess."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_tpu.core.registry import MODELS
from deeplearning_tpu.models.detection import fcos as F
from deeplearning_tpu.models.detection import yolox as Y

IMG = 128


class TestFCOS:
    def test_locations_and_forward(self):
        locs, lvl = F.fcos_locations((IMG, IMG))
        expect = sum((IMG // s) ** 2 for s in F.STRIDES if s <= IMG) + \
            sum(1 for s in F.STRIDES if s > IMG)
        assert len(locs) == expect
        model = MODELS.build("fcos_resnet18_fpn", num_classes=5,
                             dtype=jnp.float32)
        x = jnp.zeros((1, IMG, IMG, 3))
        variables = model.init(jax.random.key(0), x, train=False)
        out = model.apply(variables, x, train=False)
        assert out["cls_logits"].shape == (1, len(locs), 5)
        assert out["ltrb"].shape == (1, len(locs), 4)
        assert (np.asarray(out["ltrb"]) >= 0).all()   # exp-scaled

    def test_target_generation(self):
        locs, lvl = F.fcos_locations((IMG, IMG))
        gt_boxes = jnp.asarray([[[20.0, 20.0, 60.0, 60.0]]])   # 40px box
        gt_labels = jnp.asarray([[2]])
        gt_valid = jnp.asarray([[True]])
        tgt = F.fcos_targets(jnp.asarray(locs), jnp.asarray(lvl),
                             gt_boxes, gt_labels, gt_valid)
        pos = np.asarray(tgt["pos"][0])
        assert pos.sum() > 0
        # positives only on the level whose range covers max ltrb (~40px
        # -> level 0, stride 8, range (-1, 64))
        assert set(np.asarray(lvl)[pos]) == {0}
        # centerness in (0, 1]
        ctr = np.asarray(tgt["ctr"][0])[pos]
        assert (ctr > 0).all() and (ctr <= 1).all()
        # cls target at positives = 2; elsewhere -1
        cls = np.asarray(tgt["cls"][0])
        assert (cls[pos] == 2).all()
        assert (cls[~pos] == -1).all()

    def test_loss_and_postprocess(self):
        locs, lvl = F.fcos_locations((IMG, IMG))
        model = MODELS.build("fcos_resnet18_fpn", num_classes=5,
                             dtype=jnp.float32)
        x = jnp.zeros((1, IMG, IMG, 3))
        variables = model.init(jax.random.key(0), x, train=False)
        out = model.apply(variables, x, train=False)
        tgt = F.fcos_targets(jnp.asarray(locs), jnp.asarray(lvl),
                             jnp.asarray([[[20.0, 20, 60, 60]]]),
                             jnp.asarray([[2]]), jnp.asarray([[True]]))
        losses = F.fcos_loss(out, tgt)
        for v in losses.values():
            assert np.isfinite(float(v))
        det = F.fcos_postprocess(out, jnp.asarray(locs), (IMG, IMG),
                                 topk=200, max_det=10, score_thresh=0.0)
        assert det["boxes"].shape == (1, 10, 4)


class TestYOLOX:
    def test_forward_and_decode(self):
        model = MODELS.build("yolox_nano", num_classes=6, dtype=jnp.float32)
        x = jnp.zeros((1, IMG, IMG, 3))
        variables = model.init(jax.random.key(0), x, train=False)
        raw = model.apply(variables, x, train=False)
        centers, strides = Y.yolox_grid((IMG, IMG))
        assert raw.shape == (1, len(centers), 5 + 6)
        dec = Y.decode_outputs(raw, jnp.asarray(centers),
                               jnp.asarray(strides))
        assert dec.shape == raw.shape
        b = np.asarray(dec[0, :, :4])
        assert (b[:, 2] >= b[:, 0]).all() and (b[:, 3] >= b[:, 1]).all()

    def test_simota_assignment_properties(self):
        centers, strides = Y.yolox_grid((IMG, IMG))
        a = len(centers)
        # synthetic decoded predictions: perfect boxes around 2 gts
        gt = np.asarray([[16.0, 16, 48, 48], [64, 64, 120, 120],
                         [0, 0, 0, 0]], np.float32)
        valid = np.asarray([True, True, False])
        labels = np.asarray([1, 3, 0])
        rng = np.random.default_rng(0)
        dec = np.zeros((a, 5 + 6), np.float32)
        cx = (centers[:, 0] + 0.5) * strides
        cy = (centers[:, 1] + 0.5) * strides
        # predictions: every anchor predicts a box centered on itself
        dec[:, 0] = cx - 12
        dec[:, 1] = cy - 12
        dec[:, 2] = cx + 12
        dec[:, 3] = cy + 12
        dec[:, 4] = 3.0          # high obj logit -> sigmoid later
        dec[:, 5:] = -3.0
        assign = Y.simota_assign(jnp.asarray(dec), jnp.asarray(centers),
                                 jnp.asarray(strides), jnp.asarray(gt),
                                 jnp.asarray(labels), jnp.asarray(valid),
                                 num_classes=6)
        fg = np.asarray(assign["fg"])
        mg = np.asarray(assign["matched_gt"])
        assert fg.sum() >= 2                      # both gts got anchors
        # all fg anchors match a VALID gt
        assert set(mg[fg]).issubset({0, 1})
        # anchors matched to gt0 are spatially near gt0
        near0 = (cx > 0) & (cx < 64) & (cy > 0) & (cy < 64)
        assert near0[fg & (mg == 0)].all()

    def test_loss_finite_and_learns_signal(self):
        centers, strides = Y.yolox_grid((64, 64))
        model = MODELS.build("yolox_nano", num_classes=4, dtype=jnp.float32)
        x = jnp.zeros((1, 64, 64, 3))
        variables = model.init(jax.random.key(0), x, train=False)
        raw = model.apply(variables, x, train=False)
        losses = jax.jit(lambda r: Y.yolox_loss(
            r, jnp.asarray(centers), jnp.asarray(strides),
            jnp.asarray([[[8.0, 8, 40, 40]]]), jnp.asarray([[2]]),
            jnp.asarray([[True]]), num_classes=4, use_l1=True))(raw)
        for k in ("iou_loss", "obj_loss", "cls_loss", "l1_loss"):
            assert np.isfinite(float(losses[k])), k
        assert int(losses["num_fg"]) >= 1
        # loss is differentiable end to end
        def total(params):
            r = model.apply({"params": params,
                             "batch_stats": variables["batch_stats"]},
                            x, train=False)
            l = Y.yolox_loss(r, jnp.asarray(centers), jnp.asarray(strides),
                             jnp.asarray([[[8.0, 8, 40, 40]]]),
                             jnp.asarray([[2]]), jnp.asarray([[True]]),
                             num_classes=4)
            return l["iou_loss"] + l["obj_loss"] + l["cls_loss"]
        g = jax.jit(jax.grad(total))(variables["params"])
        gn = np.sqrt(sum(float(jnp.sum(v ** 2))
                         for v in jax.tree.leaves(g)))
        assert np.isfinite(gn) and gn > 0

    def test_postprocess_shapes(self):
        centers, strides = Y.yolox_grid((64, 64))
        rng = np.random.default_rng(0)
        raw = jnp.asarray(rng.normal(0, 1, (2, len(centers), 5 + 4)),
                          jnp.float32)
        det = Y.yolox_postprocess(raw, jnp.asarray(centers),
                                  jnp.asarray(strides), max_det=20)
        assert det["boxes"].shape == (2, 20, 4)
        assert det["valid"].shape == (2, 20)
