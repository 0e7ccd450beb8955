#!/usr/bin/env python
"""Detection training CLI: RetinaNet / YOLOX / FCOS with COCO evaluation.

  python tools/train_detection.py [--cfg FILE] [key value ...]
  JAX_PLATFORMS=cpu python tools/train_detection.py train.steps=60
  ... model.name=yolox_s train.multiscale=true   # bucketed random_resize

The detection successor of the per-project train entries
(detection/RetinaNet/train.py, fasterRcnn/train_resnet50_fpn.py,
YOLOX/tools/train.py): builds the detector, dispatches the family's
loss/postprocess (anchor-based focal, SimOTA, or FCOS targets), trains
on padded fixed-shape box batches (synthetic colored-box data by
default; npz with images/boxes/labels/valid otherwise), then runs
fixed-shape postprocess + the COCO evaluator with the native C++
matching path and prints the 12-metric summary. ``train.multiscale``
enables the bucketed-static-shape random_resize schedule
(train/multiscale.py).
"""

from __future__ import annotations

import dataclasses
import os
import sys
from typing import Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class DetModelCfg:
    name: str = "retinanet_resnet18_fpn"
    num_classes: int = 3
    image_size: int = 128
    backbone_frozen_bn: bool = False  # FrozenBatchNorm2d backbone stats
                                      # (fasterRcnn resnet50_fpn.py:5);
                                      # pair with train.freeze=backbone
                                      # for reference fine-tune semantics
    rcnn_post_nms_top_n: int = 256    # fasterrcnn proposals kept after
                                      # NMS (rpn_function.py post_nms_top_n)
    rcnn_roi_batch: int = 128         # fasterrcnn sampled rois per image
                                      # (roi_head batch_size_per_image)
    nms_impl: str = "auto"            # NMS path for every postprocess
                                      # (ops/nms.py): auto | blocked |
                                      # pallas | greedy


@dataclasses.dataclass(frozen=True)
class DetDataCfg:
    npz: Optional[str] = None
    coco: Optional[str] = None       # instances.json (real JPEG path)
    coco_images: Optional[str] = None  # default: <json dir>/images
    n_train: int = 32
    max_gt: int = 4
    batch: int = 8
    mosaic: bool = False             # 4-image mosaic per sample
    random_perspective: bool = False  # yolov5 geometric aug inside mosaic
    degrees: float = 0.0             # hyp.scratch.yaml values
    translate: float = 0.1
    scale: float = 0.5
    shear: float = 0.0
    val_rate: float = 0.1            # coco-mode eval split
    num_workers: int = 8             # coco-mode decode threads
    prefetch: int = 2                # device-feed queue depth (0 = off)


@dataclasses.dataclass(frozen=True)
class DetTrainCfg:
    steps: int = 100
    lr: float = 1e-3
    clip_grad_norm: float = 1.0
    freeze: str = ""                  # comma-separated param-path patterns
                                      # (e.g. "backbone"), yolov5 --freeze
    seed: int = 0
    eval_score_thresh: float = 0.3
    eval_tta: bool = False            # ALSO eval with multi-scale+flip
                                      # TTA (YOLOX family only)
    multiscale: bool = False          # bucketed random_resize schedule
    multiscale_min: float = 0.75      # bucket range as ratios of image_size
    multiscale_max: float = 1.25
    multiscale_every: int = 10        # steps between size changes
    no_aug_steps: int = 0             # close mosaic/perspective for the
                                      # LAST N steps and (YOLOX) add the
                                      # L1 loss — the step-based analog of
                                      # the reference's no_aug_epochs
                                      # close-mosaic schedule
                                      # (YOLOX/yolox/core/trainer.py:187-202)


@dataclasses.dataclass(frozen=True)
class DetConfig:
    model: DetModelCfg = dataclasses.field(default_factory=DetModelCfg)
    data: DetDataCfg = dataclasses.field(default_factory=DetDataCfg)
    train: DetTrainCfg = dataclasses.field(default_factory=DetTrainCfg)


def synthetic_boxes(n: int, size: int, num_classes: int, max_gt: int,
                    seed: int = 0):
    """Images with 1-2 colored squares; the class is the color channel."""
    rng = np.random.default_rng(seed)
    images = rng.normal(0, 0.05, (n, size, size, 3)).astype(np.float32)
    boxes = np.zeros((n, max_gt, 4), np.float32)
    labels = np.zeros((n, max_gt), np.int64)
    valid = np.zeros((n, max_gt), bool)
    for i in range(n):
        for g in range(rng.integers(1, 3)):
            w = rng.integers(size // 5, size // 2)
            h = rng.integers(size // 5, size // 2)
            x0 = rng.integers(0, size - w)
            y0 = rng.integers(0, size - h)
            cls = rng.integers(0, min(num_classes, 3))
            images[i, y0:y0 + h, x0:x0 + w, cls] += 1.5
            boxes[i, g] = (x0, y0, x0 + w, y0 + h)
            labels[i, g] = cls
            valid[i, g] = True
    return images, boxes, labels, valid


def build_task(model, name: str, num_classes: int, score_thresh: float,
               max_det: int = 10, rcnn_kw: Optional[dict] = None,
               nms_impl: str = "auto"):
    """Family dispatch. Returns
    (loss_fn(params, stats, batch, rng) -> (total_loss, new_stats),
     predict_fn(params, stats, images) -> padded det dict).
    The image size is read from the traced batch shape, so grids/anchors
    are rebuilt per multi-scale bucket. ``rcnn_kw``: fasterrcnn sizing
    (post_nms_top_n, roi_batch). ``nms_impl`` selects the suppression
    path for every family's postprocess (ops/nms.py).

    The predict half delegates to
    ``models/detection/predict.build_predict_fn`` — the one shared
    definition of each family's postprocessed forward, so training eval
    and the serving engine decode identically."""
    from deeplearning_tpu.models.detection.predict import build_predict_fn
    rcnn_kw = rcnn_kw or {}
    predict_fn = build_predict_fn(
        model, name, num_classes, score_thresh=score_thresh,
        max_det=max_det,
        post_nms_top_n=rcnn_kw.get("post_nms_top_n",
                                   DetModelCfg.rcnn_post_nms_top_n),
        nms_impl=nms_impl)

    def apply_train(params, stats, images, **kw):
        out, mut = model.apply({"params": params, "batch_stats": stats},
                               images, train=True,
                               mutable=["batch_stats"], **kw)
        return out, mut.get("batch_stats", stats)

    if name.startswith("retinanet"):
        from deeplearning_tpu.models.detection.retinanet import (
            retinanet_anchors, retinanet_loss, retinanet_postprocess)

        def loss_fn(params, stats, batch, rng):
            hw = batch["image"].shape[1:3]
            out, new_stats = apply_train(params, stats, batch["image"])
            l = retinanet_loss(out, jnp.asarray(retinanet_anchors(hw)),
                               batch["boxes"], batch["labels"],
                               batch["valid"])
            return l["cls_loss"] + l["reg_loss"], new_stats

        return loss_fn, predict_fn

    if name.startswith("yolox"):
        from deeplearning_tpu.models.detection.yolox import (
            yolox_grid, yolox_loss)

        def loss_fn(params, stats, batch, rng, use_l1=False):
            hw = batch["image"].shape[1:3]
            centers, strides = (jnp.asarray(a) for a in yolox_grid(hw))
            out, new_stats = apply_train(params, stats, batch["image"])
            l = yolox_loss(out, centers, strides, batch["boxes"],
                           batch["labels"], batch["valid"],
                           num_classes=num_classes, use_l1=use_l1)
            return (l["iou_loss"] + l["obj_loss"] + l["cls_loss"]
                    + l["l1_loss"], new_stats)

        return loss_fn, predict_fn

    if name.startswith("yolov5"):
        from deeplearning_tpu.models.detection.yolov5 import (
            yolov5_grid, yolov5_loss)

        def loss_fn(params, stats, batch, rng):
            hw = batch["image"].shape[1:3]
            grid = {k: jnp.asarray(v)
                    for k, v in yolov5_grid(hw).items()}
            out, new_stats = apply_train(params, stats, batch["image"])
            l = yolov5_loss(out, grid, batch["boxes"], batch["labels"],
                            batch["valid"], num_classes=num_classes)
            return (l["box_loss"] + l["obj_loss"] + l["cls_loss"],
                    new_stats)

        return loss_fn, predict_fn

    if name.startswith("fcos"):
        from deeplearning_tpu.models.detection.fcos import (
            fcos_locations, fcos_loss, fcos_targets)

        def loss_fn(params, stats, batch, rng):
            hw = batch["image"].shape[1:3]
            locs, lvl = (jnp.asarray(a) for a in fcos_locations(hw))
            out, new_stats = apply_train(params, stats, batch["image"])
            tgt = fcos_targets(locs, lvl, batch["boxes"], batch["labels"],
                               batch["valid"])
            l = fcos_loss(out, tgt)
            return (l["cls_loss"] + l["reg_loss"] + l["ctr_loss"],
                    new_stats)

        return loss_fn, predict_fn

    if name.startswith("fasterrcnn"):
        # two-stage: RPN loss on the first apply, proposals sampled
        # under stop-gradient semantics, RoI-head loss on a second apply
        # that REUSES the first call's pyramid (one backbone forward per
        # step, train_resnet50_fpn.py flow). The model's class space is
        # num_classes+1 with 0 = background, so gt labels shift +1 here
        # and detections shift -1 back in predict.
        from deeplearning_tpu.models.detection.faster_rcnn import (
            fasterrcnn_anchors, generate_proposals, roi_head_loss,
            rpn_loss, sample_rois)
        # fall back to the DetModelCfg defaults (single source of truth
        # for callers like demo.py that pass no rcnn_kw)
        post_nms = rcnn_kw.get("post_nms_top_n",
                               DetModelCfg.rcnn_post_nms_top_n)
        roi_batch = rcnn_kw.get("roi_batch", DetModelCfg.rcnn_roi_batch)

        def loss_fn(params, stats, batch, rng):
            hw = batch["image"].shape[1:3]
            anchors = jnp.asarray(fasterrcnn_anchors(hw))
            labels1 = jnp.where(batch["valid"], batch["labels"] + 1, 0)
            out, stats1 = apply_train(params, stats, batch["image"])
            r = rpn_loss(out, anchors, batch["boxes"], batch["valid"],
                         rng)
            props, pvalid = generate_proposals(out, anchors, hw,
                                               post_nms_top_n=post_nms,
                                               nms_impl=nms_impl)
            samples = sample_rois(
                jax.lax.stop_gradient(props), pvalid, batch["boxes"],
                labels1, batch["valid"], rng,
                batch_per_image=roi_batch)
            # second stage on the SAME pyramid: no backbone recompute,
            # stats1 stays the step's final batch_stats (the roi pass
            # runs no BN)
            out2, _ = apply_train(params, stats1, batch["image"],
                                  proposals=samples["rois"],
                                  pyramid=out["pyramid"])
            h = roi_head_loss(out2["roi_scores"], out2["roi_deltas"],
                              samples)
            return (r["rpn_obj_loss"] + r["rpn_reg_loss"]
                    + h["roi_cls_loss"] + h["roi_reg_loss"], stats1)

        return loss_fn, predict_fn

    raise ValueError(f"no detection task for model {name!r} "
                     "(expected retinanet*/fasterrcnn*/yolox*/fcos*)")


def main(argv=None) -> int:
    # --exp NAME: seed the config DEFAULTS from a registered DetectionExp
    # (exps/default/* analog). Precedence: defaults < exp < yaml < CLI.
    from deeplearning_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()   # step compiles are once-per-machine, not per-run
    from deeplearning_tpu.core.config import config_cli, pop_flag
    argv = list(sys.argv[1:] if argv is None else argv)
    evolve_gens = pop_flag(argv, "--evolve")
    exp_name = pop_flag(argv, "--exp")
    defaults = DetConfig()
    if exp_name:
        from deeplearning_tpu.core.config import load_config
        from deeplearning_tpu.core.experiment import get_exp
        defaults = load_config(
            defaults, None, get_exp(exp_name=exp_name).cli_overrides())
    cfg = config_cli(defaults, argv, description=__doc__)

    if evolve_gens:
        # yolov5 --evolve analog: short training runs as the fitness
        # probe, JSONL records in runs/evolve, best hyp printed at the
        # end. Evolvable genes = the DetTrainCfg fields in the meta.
        from deeplearning_tpu.train.evolve import (DETECTION_META,
                                                   det_fitness, evolve)

        def eval_fn(hyp):
            trial = dataclasses.replace(
                cfg, train=dataclasses.replace(
                    cfg.train, lr=hyp["lr"],
                    clip_grad_norm=hyp["clip_grad_norm"]))
            return det_fitness(run(trial))

        meta = {"lr": DETECTION_META["lr"],
                "clip_grad_norm": (1.0, 0.1, 10.0)}
        best = evolve(eval_fn,
                      {"lr": cfg.train.lr,
                       "clip_grad_norm": cfg.train.clip_grad_norm},
                      meta, int(evolve_gens),
                      records_path="runs/evolve/detection.jsonl",
                      seed=cfg.train.seed)
        print(f"evolve done: best hyp {best}")
        return 0

    run(cfg)
    return 0


def run(cfg) -> dict:
    """Train + evaluate one configuration; returns the COCO summary."""
    import optax

    from deeplearning_tpu.core.registry import MODELS
    from deeplearning_tpu.evaluation.coco_eval import CocoEvaluator
    from deeplearning_tpu.train.multiscale import (MultiScaleSchedule,
                                                   resize_detection_batch)

    size = cfg.model.image_size
    num_classes = cfg.model.num_classes
    if cfg.train.eval_tta and not cfg.model.name.startswith("yolox"):
        raise ValueError("train.eval_tta currently supports the "
                         "YOLOX family")   # fail BEFORE training
    eval_max_det = 10
    train_src = val_src = None
    persp = (dict(degrees=cfg.data.degrees, translate=cfg.data.translate,
                  scale=cfg.data.scale, shear=cfg.data.shear)
             if cfg.data.random_perspective else None)
    if cfg.data.coco:
        from deeplearning_tpu.data.coco import (coco_detection_source,
                                                load_coco_json)
        from deeplearning_tpu.data.loader import MapSource
        records, class_names = load_coco_json(cfg.data.coco)
        images_dir = cfg.data.coco_images or os.path.join(
            os.path.dirname(cfg.data.coco), "images")
        if cfg.model.num_classes != len(class_names):
            raise ValueError(
                f"model.num_classes={cfg.model.num_classes} but "
                f"{cfg.data.coco} has {len(class_names)} categories — "
                "set model.num_classes to match")
        num_classes = len(class_names)
        order = np.random.default_rng(cfg.train.seed).permutation(
            len(records))
        n_val = max(int(len(records) * cfg.data.val_rate), 1)
        val_idx, tr_idx = order[:n_val], order[n_val:]
        aug_src, _ = coco_detection_source(
            images_dir=images_dir, records=records,
            class_names=class_names, image_size=size,
            max_gt=cfg.data.max_gt, augment=True, seed=cfg.train.seed,
            mosaic=cfg.data.mosaic, perspective=persp,
            # extra mosaic tiles must come from the TRAIN split only —
            # drawing from all records would train on held-out val images
            mosaic_pool=tr_idx)
        raw_src, _ = coco_detection_source(
            images_dir=images_dir, records=records,
            class_names=class_names, image_size=size,
            max_gt=cfg.data.max_gt, augment=False)
        train_src = MapSource(len(tr_idx),
                              lambda i: aug_src[int(tr_idx[i])])
        val_src = MapSource(len(val_idx),
                            lambda i: raw_src[int(val_idx[i])])
    elif cfg.data.npz:
        blob = np.load(cfg.data.npz)
        images, boxes, labels, valid = (blob["images"], blob["boxes"],
                                        blob["labels"], blob["valid"])
    else:
        images, boxes, labels, valid = synthetic_boxes(
            cfg.data.n_train, size, cfg.model.num_classes,
            cfg.data.max_gt, cfg.train.seed)
    if cfg.data.mosaic and train_src is None:
        # npz/synthetic arrays: every sample becomes a fresh mosaic
        from deeplearning_tpu.data.mixup import mosaic_array_source
        train_src = mosaic_array_source(
            images, boxes, labels, valid, out_size=size,
            max_boxes=cfg.data.max_gt, seed=cfg.train.seed,
            perspective=persp, fill=float(np.median(images[0])))

    # close-mosaic (trainer.py:187-202 close_mosaic): a geometric-aug-free
    # source for the final no_aug_steps. coco mode keeps the photometric
    # augs and drops mosaic/perspective; array modes fall back to the raw
    # arrays (built below, where the array batch fn lives).
    plain_src = None
    if cfg.train.no_aug_steps > 0 and cfg.data.coco and (
            cfg.data.mosaic or cfg.data.random_perspective):
        plain_aug, _ = coco_detection_source(
            images_dir=images_dir, records=records,
            class_names=class_names, image_size=size,
            max_gt=cfg.data.max_gt, augment=True, seed=cfg.train.seed + 1)
        plain_src = MapSource(len(tr_idx),
                              lambda i: plain_aug[int(tr_idx[i])])

    model_classes = num_classes + (
        1 if cfg.model.name.startswith("fasterrcnn") else 0)  # +background
    model_kw = {}
    if cfg.model.backbone_frozen_bn:
        model_kw["backbone_frozen_bn"] = True
    model = MODELS.build(cfg.model.name, num_classes=model_classes,
                         **model_kw)
    loss_fn_task, predict_fn = build_task(
        model, cfg.model.name, num_classes, cfg.train.eval_score_thresh,
        max_det=eval_max_det,
        rcnn_kw=dict(post_nms_top_n=cfg.model.rcnn_post_nms_top_n,
                     roi_batch=cfg.model.rcnn_roi_batch),
        nms_impl=cfg.model.nms_impl)
    variables = model.init(jax.random.key(cfg.train.seed),
                           jnp.zeros((1, size, size, 3)), train=False)
    params, stats = variables["params"], variables.get("batch_stats", {})
    from deeplearning_tpu.train.optim import build_optimizer
    tx = build_optimizer(
        "adam", cfg.train.lr, clip_grad_norm=cfg.train.clip_grad_norm,
        params=params,
        freeze=tuple(p.strip() for p in cfg.train.freeze.split(",")
                     if p.strip()) or None)
    opt_state = tx.init(params)

    schedule = None
    if cfg.train.multiscale:
        lo = int(size * cfg.train.multiscale_min) // 32 * 32
        hi = int(size * cfg.train.multiscale_max) // 32 * 32
        sizes = tuple(range(max(lo, 32), hi + 1, 32)) or (size,)
        schedule = MultiScaleSchedule(sizes=sizes,
                                      change_every=cfg.train.multiscale_every,
                                      seed=cfg.train.seed)

    import functools

    @functools.partial(jax.jit, static_argnames=("use_l1",))
    def step(params, opt_state, stats, batch, key, use_l1=False):
        def loss_fn(p):
            if use_l1:
                return loss_fn_task(p, stats, batch, key, use_l1=True)
            return loss_fn_task(p, stats, batch, key)
        (total, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                new_stats, total)

    rng = np.random.default_rng(cfg.train.seed)
    key = jax.random.key(cfg.train.seed)

    def make_loader_fn(src, seed):
        from deeplearning_tpu.data.device_prefetch import DevicePrefetcher
        from deeplearning_tpu.data.loader import DataLoader
        loader = DataLoader(src, cfg.data.batch, shuffle=True, seed=seed,
                            infinite=True,
                            num_workers=cfg.data.num_workers)
        if cfg.data.prefetch:
            # decode + H2D run on the prefetch worker thread, overlapped
            # with the previous step's compute; the old shape blocked on
            # a per-leaf jnp.asarray transfer inside the step loop
            it = iter(DevicePrefetcher(loader, depth=cfg.data.prefetch))
            return lambda: next(it)
        it = iter(loader)
        return lambda: {k: jnp.asarray(v) for k, v in next(it).items()}

    def make_array_fn():
        n = len(images)

        def fn():
            idx = rng.choice(n, cfg.data.batch, replace=False)
            return {"image": jnp.asarray(images[idx]),
                    "boxes": jnp.asarray(boxes[idx]),
                    "labels": jnp.asarray(labels[idx]),
                    "valid": jnp.asarray(valid[idx])}
        return fn

    next_batch = (make_loader_fn(train_src, cfg.train.seed)
                  if train_src is not None else make_array_fn())
    if cfg.train.no_aug_steps >= max(cfg.train.steps, 1):
        raise ValueError(
            f"train.no_aug_steps={cfg.train.no_aug_steps} must be < "
            f"train.steps={cfg.train.steps} (it is the length of the "
            "FINAL aug-free phase)")
    aug_close_at = (cfg.train.steps - cfg.train.no_aug_steps
                    if cfg.train.no_aug_steps > 0 else None)
    next_batch_plain = next_batch
    if aug_close_at is not None:
        if plain_src is not None:
            next_batch_plain = make_loader_fn(plain_src,
                                              cfg.train.seed + 1)
        elif train_src is not None and not cfg.data.coco:
            next_batch_plain = make_array_fn()   # raw npz/synthetic arrays
    is_yolox = cfg.model.name.startswith("yolox")

    for it in range(cfg.train.steps):
        closing = aug_close_at is not None and it >= aug_close_at
        if closing and it == aug_close_at:
            print(f"step {it}: closing mosaic/perspective"
                  + (" + adding L1 loss" if is_yolox else ""))
        batch = (next_batch_plain if closing else next_batch)()
        if schedule is not None:
            batch = resize_detection_batch(batch,
                                           schedule.size_for_step(it))
        params, opt_state, stats, total = step(
            params, opt_state, stats, batch, jax.random.fold_in(key, it),
            use_l1=bool(closing and is_yolox))
        if it % max(cfg.train.steps // 5, 1) == 0:
            print(f"step {it}: loss={float(total):.4f}")

    # ---- evaluate: coco mode on the held-out split, else train set.
    # One jitted batched postprocess per eval step; the whole padded
    # batch lands on the host in one transfer (CocoEvaluator.add_batch),
    # no per-image device slicing.
    def eval_with(pred_fn, tag=""):
        ev = CocoEvaluator(num_classes=num_classes)
        pred_jit = jax.jit(pred_fn)
        if val_src is not None:
            bs = cfg.data.batch
            n_val = len(val_src)
            for start in range(0, n_val, bs):
                # pad the tail chunk to the jitted batch shape, score
                # only the real images
                idx = np.minimum(np.arange(start, start + bs), n_val - 1)
                n_real = min(bs, n_val - start)
                sample = val_src[idx]
                det = pred_jit(params, stats,
                               jnp.asarray(sample["image"]))
                ev.add_batch(
                    np.arange(start, start + bs), det,
                    gt={"boxes": sample["boxes"],
                        "labels": sample["labels"],
                        "valid": sample["valid"]},
                    image_valid=np.arange(bs) < n_real)
        else:
            det = pred_jit(params, stats, jnp.asarray(images))
            ev.add_batch(np.arange(len(images)), det,
                         gt={"boxes": boxes, "labels": labels,
                             "valid": valid})
        summary = ev.summarize()
        print(tag + str({k: round(v, 4) for k, v in summary.items()}))
        return summary

    summary = eval_with(predict_fn)
    if cfg.train.eval_tta:
        from deeplearning_tpu.ops.tta import yolox_tta

        def predict_tta(p, st, imgs):
            raw_fn = lambda x: model.apply(
                {"params": p, "batch_stats": st}, x, train=False)
            return yolox_tta(raw_fn, imgs,
                             score_thresh=cfg.train.eval_score_thresh,
                             max_det=eval_max_det)
        summary_tta = eval_with(predict_tta, tag="TTA ")
        summary = {**summary, "tta": summary_tta}
    return summary


if __name__ == "__main__":
    raise SystemExit(main())
