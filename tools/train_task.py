#!/usr/bin/env python
"""Unified task CLI for the non-classification families — the successor
of the reference's per-project train.py entries: Image_segmentation/*/
train.py, self-supervised/MAE/train.py, self-supervised/SupCon (trainer/
trainer.py), metric_learning/BDB/main.py, pose_estimation/Insulator/
train.py, deep_stereo Stereo_Online_Adaptation.py.

Usage:
  python tools/train_task.py --task segmentation model.name=unet
  python tools/train_task.py --task mae train.steps=20
  python tools/train_task.py --task supcon
  python tools/train_task.py --task metric
  python tools/train_task.py --task keypoints
  python tools/train_task.py --task stereo

Each task trains on synthetic (or npz) data with the family's loss and
prints a task metric at the end — the smoke-train surface the reference
covers with its bundled mini-datasets.
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
class ModelCfg:
    name: str = ""                   # per-task default if empty
    num_classes: int = 4
    image_size: int = 32


@dataclasses.dataclass(frozen=True)
class DataCfg:
    npz: Optional[str] = None
    n_train: int = 32
    batch: int = 8


@dataclasses.dataclass(frozen=True)
class TrainCfg:
    steps: int = 30
    lr: float = 1e-3
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class TaskConfig:
    model: ModelCfg = dataclasses.field(default_factory=ModelCfg)
    data: DataCfg = dataclasses.field(default_factory=DataCfg)
    train: TrainCfg = dataclasses.field(default_factory=TrainCfg)


DEFAULT_MODEL = {
    "segmentation": "unet",
    "mae": "mae_vit_small_patch16",
    "supcon": "supcon_resnet18",
    "metric": "arcface_resnet18",
    "keypoints": "hrnet_w18_keypoints",
    "stereo": "madnet",
}


def _loop(loss_fn, params, steps, lr):
    """Shared Adam loop: loss_fn(params, step) -> scalar loss."""
    import optax
    tx = optax.adam(lr)
    opt = tx.init(params)

    @jax.jit
    def step(params, opt, i):
        loss, g = jax.value_and_grad(lambda p: loss_fn(p, i))(params)
        up, opt = tx.update(g, opt, params)
        return optax.apply_updates(params, up), opt, loss

    first = last = None
    for i in range(steps):
        params, opt, loss = step(params, opt, jnp.asarray(i))
        last = float(loss)
        if first is None:
            first = last
        if i % max(steps // 5, 1) == 0:
            print(f"step {i}: loss={last:.4f}", flush=True)
    if last is None:
        print("no steps run")
        return params, float("nan"), float("nan")
    print(f"loss {first:.4f} -> {last:.4f}")
    return params, first, last


def _load_npz_images(blob):
    """images from an npz: uint8 -> [0,1] float, grayscale -> RGB."""
    images = blob["images"]
    if images.dtype == np.uint8:
        images = images.astype(np.float32) / 255.0
    if images.ndim == 3:
        images = np.repeat(images[..., None], 3, axis=-1)
    return images


def _make_batcher(batch, *arrays):
    """Deterministic wraparound minibatcher over equally-indexed arrays
    (jit-safe: dynamic_slice with the traced step index)."""
    b = min(batch, arrays[0].shape[0])

    def batch_at(i):
        start = (i * b) % (arrays[0].shape[0] - b + 1)
        return tuple(jax.lax.dynamic_slice_in_dim(a, start, b)
                     for a in arrays)
    return batch_at


def _chunked_apply(n_total, batch):
    """Yield (idx, n_real) chunks covering [0, n_total) at a fixed jit
    batch shape: tail chunks pad by clamping to the last index and the
    caller counts only the first n_real rows."""
    eb = min(batch, n_total)
    for start in range(0, n_total, eb):
        idx = np.minimum(np.arange(start, start + eb), n_total - 1)
        yield idx, min(eb, n_total - start)


def _pk_order(labels_all):
    """K=2 same-id instances adjacent, ids cycling — every wraparound
    batch then has both positives AND negatives (a label-sorted order
    degenerates contrastive/triplet objectives: no negatives)."""
    by_id = np.argsort(labels_all, kind="stable")
    within = np.zeros(len(labels_all), np.int64)
    counts = {}
    for pos, idx in enumerate(by_id):
        c = int(labels_all[idx])
        within[pos] = counts.get(c, 0)
        counts[c] = counts.get(c, 0) + 1
    return by_id[np.lexsort((within % 2, labels_all[by_id],
                             within // 2))]


def run_segmentation(cfg: TaskConfig) -> int:
    from deeplearning_tpu.core.registry import MODELS
    from deeplearning_tpu.evaluation.metrics import (confusion_matrix,
                                                     miou_from_confusion)
    from deeplearning_tpu.ops import losses as L

    if cfg.data.npz:
        # real-data path: npz with images (N,H,W,3) f32 and masks
        # (N,H,W) int; first 10% held out for the mIoU report
        blob = np.load(cfg.data.npz)
        images = _load_npz_images(blob)
        masks = blob["masks"].astype(np.int32)
        num_classes = int(masks.max()) + 1
        n_val = max(len(images) // 10, 1)
        val_x, val_y = images[:n_val], masks[:n_val]
        tr_x = jnp.asarray(images[n_val:])
        tr_y = jnp.asarray(masks[n_val:])
        batch_at = _make_batcher(cfg.data.batch, tr_x, tr_y)
        init_x = tr_x[:1]
    else:
        s = cfg.model.image_size
        rng = np.random.default_rng(cfg.train.seed)
        x = rng.normal(0, 0.1, (cfg.data.batch, s, s, 3)).astype(
            np.float32)
        y = np.zeros((cfg.data.batch, s, s), np.int32)
        for i in range(cfg.data.batch):
            cx, cy, r = rng.integers(8, s - 8), rng.integers(8, s - 8), 6
            yy, xx = np.mgrid[:s, :s]
            m = (yy - cy) ** 2 + (xx - cx) ** 2 < r * r
            y[i][m] = 1
            x[i][m] += 1.0
        tr_x, tr_y = jnp.asarray(x), jnp.asarray(y)
        val_x, val_y = x, y
        num_classes = 2
        batch_at = lambda i: (tr_x, tr_y)
        init_x = tr_x[:1]

    model = MODELS.build(cfg.model.name or "unet",
                         num_classes=num_classes, dtype=jnp.float32)
    variables = model.init(jax.random.key(0), init_x, train=False)
    params, stats = variables["params"], variables.get("batch_stats", {})

    def loss_fn(p, i):
        bx, by = batch_at(i)
        out = model.apply({"params": p, "batch_stats": stats}, bx,
                          train=False)
        logits = out[0] if isinstance(out, tuple) else out
        return L.cross_entropy(logits, by) + L.dice_loss(logits, by)

    params, first, last = _loop(loss_fn, params, cfg.train.steps,
                                cfg.train.lr)

    @jax.jit
    def predict(p, bx):
        out = model.apply({"params": p, "batch_stats": stats}, bx,
                          train=False)
        return jnp.argmax(out[0] if isinstance(out, tuple) else out, -1)

    mat = np.zeros((num_classes, num_classes), np.int64)
    for idx, n_real in _chunked_apply(len(val_x), cfg.data.batch):
        pred = predict(params, jnp.asarray(val_x[idx]))
        mat += np.asarray(confusion_matrix(
            pred[:n_real], jnp.asarray(val_y[idx][:n_real]),
            num_classes))
    miou = miou_from_confusion(mat)["miou"]
    print(f"task_metric miou={float(miou):.4f}")
    return 0 if np.isfinite(last) else 1


def run_mae(cfg: TaskConfig) -> int:
    from deeplearning_tpu.core.registry import MODELS

    if cfg.data.npz:
        # real-data pretraining: npz images, wraparound minibatches
        images = _load_npz_images(np.load(cfg.data.npz))
        tr_x = jnp.asarray(images)
        batch_at = _make_batcher(cfg.data.batch, tr_x)
        init_x = tr_x[:1]
    else:
        s = max(cfg.model.image_size, 32)
        tr_x = jnp.asarray(np.random.default_rng(cfg.train.seed).normal(
            size=(cfg.data.batch, s, s, 3)), jnp.float32)
        batch_at = lambda i: (tr_x,)
        init_x = tr_x
    model = MODELS.build(cfg.model.name or "mae_vit_small_patch16",
                         dtype=jnp.float32, depth=2, decoder_depth=2)
    variables = model.init(
        {"params": jax.random.key(0), "masking": jax.random.key(1)},
        init_x, train=False)

    def loss_fn(p, i):
        (bx,) = batch_at(i)
        loss, _, _ = model.apply(
            {"params": p}, bx, train=True,
            rngs={"masking": jax.random.fold_in(jax.random.key(5), i),
                  "dropout": jax.random.fold_in(jax.random.key(6), i)})
        return loss

    _, first, last = _loop(loss_fn, variables["params"], cfg.train.steps,
                           cfg.train.lr)
    print(f"task_metric mae_recon_loss={last:.4f}")
    return 0 if np.isfinite(last) else 1


def run_supcon(cfg: TaskConfig) -> int:
    from deeplearning_tpu.core.registry import MODELS
    from deeplearning_tpu.ops import losses as L

    rng = np.random.default_rng(cfg.train.seed)
    if cfg.data.npz:
        # real-data path: npz {images, labels}; the second view is a
        # horizontal flip (two-view supervised-contrastive batches)
        blob = np.load(cfg.data.npz)
        images = _load_npz_images(blob)
        labels_all = blob["labels"].astype(np.int32)
        order = _pk_order(labels_all)   # mixed-class batches (negatives)
        images, labels_all = images[order], labels_all[order]
        tr_x = jnp.asarray(images)
        tr_y = jnp.asarray(labels_all)
        batch_at = _make_batcher(cfg.data.batch, tr_x, tr_y)
        init_x = tr_x[:1]
        two_views = lambda bx: (bx, bx[:, :, ::-1, :])
    else:
        s = cfg.model.image_size
        labels = np.repeat(np.arange(max(cfg.data.batch // 2, 1)), 2)
        base = rng.normal(0, 0.2,
                          (len(labels), s, s, 3)).astype(np.float32)
        base[np.arange(len(labels)), labels * 3 % s,
             labels * 3 % s, :] += 2.0
        tr_x, tr_y = jnp.asarray(base), jnp.asarray(labels)
        batch_at = lambda i: (tr_x, tr_y)
        init_x = tr_x[:1]
        two_views = lambda bx: (bx, bx)     # two-view stand-in

    model = MODELS.build(cfg.model.name or "supcon_resnet18",
                         num_classes=cfg.model.num_classes,
                         dtype=jnp.float32)
    variables = model.init(jax.random.key(0), init_x, train=False)
    params, stats = variables["params"], variables.get("batch_stats", {})

    def loss_fn(p, i):
        bx, by = batch_at(i)
        va, vb = two_views(bx)
        za = model.apply({"params": p, "batch_stats": stats}, va,
                         train=False)
        zb = model.apply({"params": p, "batch_stats": stats}, vb,
                         train=False)
        feats = jnp.stack([za, zb], axis=1)
        return L.supcon_loss(feats, by)

    _, first, last = _loop(loss_fn, params, cfg.train.steps, cfg.train.lr)
    print(f"task_metric supcon_loss={last:.4f}")
    return 0 if np.isfinite(last) else 1


def run_metric(cfg: TaskConfig) -> int:
    from deeplearning_tpu.core.registry import MODELS
    from deeplearning_tpu.evaluation.retrieval import (cmc_map,
                                                       pairwise_distances)
    from deeplearning_tpu.ops import losses as L

    s = cfg.model.image_size
    rng = np.random.default_rng(cfg.train.seed)
    if cfg.data.npz:
        # real-data path: npz with images (N,H,W[,3]) and labels (N,)
        # identity labels; PK-style batches come from the wraparound
        # batcher over a label-sorted order (ids stay adjacent)
        blob = np.load(cfg.data.npz)
        images = _load_npz_images(blob)
        labels_all = blob["labels"].astype(np.int32)
        order = _pk_order(labels_all)
        images, labels_all = images[order], labels_all[order]
        n_id = int(labels_all.max()) + 1
        tr_x = jnp.asarray(images)
        tr_y = jnp.asarray(labels_all)
        batch_at = _make_batcher(cfg.data.batch, tr_x, tr_y)
        x, y = tr_x, tr_y          # eval embeds the whole set below
        init_x = tr_x[:1]
    else:
        n_id = cfg.model.num_classes
        labels = np.repeat(np.arange(n_id),
                           max(cfg.data.batch // n_id, 2))
        xx = rng.normal(0, 0.2, (len(labels), s, s, 3)).astype(
            np.float32)
        for i, lab in enumerate(labels):
            xx[i, :, lab * 4 % s:(lab * 4 % s) + 3, :] += 1.5
        x, y = jnp.asarray(xx), jnp.asarray(labels)
        batch_at = lambda i: (x, y)
        init_x = x[:1]

    model = MODELS.build(cfg.model.name or "arcface_resnet18",
                         num_classes=n_id, dtype=jnp.float32)
    variables = model.init(jax.random.key(0), init_x, train=False)
    params, stats = variables["params"], variables.get("batch_stats", {})

    def loss_fn(p, i):
        bx, by = batch_at(i)
        out = model.apply({"params": p, "batch_stats": stats}, bx,
                          train=False)
        emb, centers = out["embedding"], out["centers"]
        logits = L.arcface_logits(emb, centers, by)
        return L.cross_entropy(logits, by) + L.triplet_loss(emb, by,
                                                            margin=0.3)

    params, first, last = _loop(loss_fn, params, cfg.train.steps,
                                cfg.train.lr)

    @jax.jit
    def embed(p, bx):
        return model.apply({"params": p, "batch_stats": stats}, bx,
                           train=False)["embedding"]

    chunks = []
    for idx, n_real in _chunked_apply(x.shape[0], cfg.data.batch):
        chunks.append(np.asarray(embed(params,
                                       jnp.asarray(x[idx])))[:n_real])
    emb = np.concatenate(chunks)
    # interleave query/gallery so every query id appears in the gallery
    # (a contiguous split would separate the id sets -> vacuous metric)
    q, g = emb[0::2], emb[1::2]
    yq, yg = np.asarray(y)[0::2], np.asarray(y)[1::2]
    dist = pairwise_distances(q, g)
    res = cmc_map(dist, yq, yg)
    print(f"task_metric rank1={res['rank1']:.4f} mAP={res['mAP']:.4f}")
    return 0 if np.isfinite(last) else 1


def run_keypoints(cfg: TaskConfig) -> int:
    from deeplearning_tpu.core.registry import MODELS
    from deeplearning_tpu.evaluation.keypoints import (decode_heatmaps,
                                                       make_heatmap_targets,
                                                       pck)
    from deeplearning_tpu.ops import losses as L

    if cfg.data.npz:
        # real-data path: npz with images (N,H,W[,3]) and keypoints
        # (N,K,3) = (x, y, vis); heatmap targets precomputed host-side
        blob = np.load(cfg.data.npz)
        images = _load_npz_images(blob)
        kps_all = blob["keypoints"].astype(np.float32)     # (N, K, 3)
        h, w = images.shape[1:3]
        s = max(h, w)                       # pck threshold scale
        k = kps_all.shape[1]
        vis_all = kps_all[..., 2]
        n_val = max(len(images) // 10, 1)
        val = (images[:n_val], kps_all[:n_val], vis_all[:n_val])
        # targets only for the TRAINING slice (val scores via pck)
        targets = np.stack([
            make_heatmap_targets(kps_all[i, :, :2], vis_all[i],
                                 (h // 4, w // 4), stride=4)
            for i in range(n_val, len(images))])
        tr_x = jnp.asarray(images[n_val:])
        tr_t = jnp.asarray(targets)
        tr_v = jnp.asarray(vis_all[n_val:])
        batch_at = _make_batcher(cfg.data.batch, tr_x, tr_t, tr_v)
        init_x = tr_x[:1]
    else:
        s = max(cfg.model.image_size, 64)
        k = 4
        rng = np.random.default_rng(cfg.train.seed)
        kps = rng.uniform(8, s - 8,
                          (cfg.data.batch, k, 2)).astype(np.float32)
        vis = np.ones((cfg.data.batch, k), np.float32)
        x = np.zeros((cfg.data.batch, s, s, 3), np.float32)
        for i in range(cfg.data.batch):
            for j in range(k):
                xx, yy = int(kps[i, j, 0]), int(kps[i, j, 1])
                x[i, max(yy - 1, 0):yy + 2,
                  max(xx - 1, 0):xx + 2, j % 3] = 2.0
        target = jnp.asarray(np.stack([
            make_heatmap_targets(kps[i], vis[i], (s // 4, s // 4),
                                 stride=4)
            for i in range(cfg.data.batch)]))
        tr_x = jnp.asarray(x)
        vis_j = jnp.asarray(vis)
        batch_at = lambda i: (tr_x, target, vis_j)
        val = (x, np.concatenate([kps, vis[..., None]], -1), vis)
        init_x = tr_x[:1]

    model = MODELS.build(cfg.model.name or "hrnet_w18_keypoints",
                         num_classes=k, dtype=jnp.float32)
    variables = model.init(jax.random.key(0), init_x, train=False)
    params, stats = variables["params"], variables.get("batch_stats", {})

    def loss_fn(p, i):
        bx, bt, bv = batch_at(i)
        heat = model.apply({"params": p, "batch_stats": stats}, bx,
                           train=False)
        return L.heatmap_mse_loss(heat, bt, bv)

    params, first, last = _loop(loss_fn, params, cfg.train.steps,
                                cfg.train.lr)

    val_x, val_kp, val_vis = val

    @jax.jit
    def predict(p, bx):
        heat = model.apply({"params": p, "batch_stats": stats}, bx,
                           train=False)
        return decode_heatmaps(heat, stride=4)[0]

    scores = []
    for idx, n_real in _chunked_apply(len(val_x), cfg.data.batch):
        pred = np.asarray(predict(params, jnp.asarray(val_x[idx])))
        scores.extend(pck(pred[i], val_kp[idx[i], :, :2],
                          val_vis[idx[i]], threshold_px=s * 0.2)
                      for i in range(n_real))
    score = float(np.mean(scores))
    print(f"task_metric pck@0.2={float(score):.4f}")
    return 0 if np.isfinite(last) else 1


def run_stereo(cfg: TaskConfig) -> int:
    from deeplearning_tpu.core.registry import MODELS
    from deeplearning_tpu.models.stereo.madnet import photometric_loss

    rng = np.random.default_rng(cfg.train.seed)
    if cfg.data.npz:
        # real-data path: npz with left/right (N,H,W[,3]) rectified pairs
        blob = np.load(cfg.data.npz)
        left = _load_npz_images({"images": blob["left"]})
        right = _load_npz_images({"images": blob["right"]})
        left, right = jnp.asarray(left), jnp.asarray(right)
    else:
        s = max(cfg.model.image_size, 64)
        b = max(cfg.data.batch, 1)
        left = rng.normal(0, 1, (b, s, s, 3)).astype(np.float32)
        right = np.roll(left, -3, axis=2)
        left, right = jnp.asarray(left), jnp.asarray(right)

    model = MODELS.build(cfg.model.name or "madnet", dtype=jnp.float32)
    params = model.init(jax.random.key(0), left, right)["params"]

    def loss_fn(p, i):
        out = model.apply({"params": p}, left, right)
        return photometric_loss(left, right, out["disparity"])

    _, first, last = _loop(loss_fn, params, cfg.train.steps, cfg.train.lr)
    print(f"task_metric photometric={last:.4f}")
    return 0 if np.isfinite(last) else 1


def run_stereo_online(cfg: TaskConfig) -> int:
    """MAD online adaptation (Stereo_Online_Adaptation.py modes): per
    'frame', sample a subset of blocks with the reward-softmax sampler
    and backprop only through them (grad mask)."""
    import optax
    from deeplearning_tpu.core.registry import MODELS
    from deeplearning_tpu.models.stereo.madnet import (MADSampler,
                                                       photometric_loss)

    rng = np.random.default_rng(cfg.train.seed)
    if cfg.data.npz:
        # real-data path: npz {left, right} frame sequences; online
        # adaptation consumes frame i%N at step i (the video-stream
        # semantics of Stereo_Online_Adaptation)
        blob = np.load(cfg.data.npz)
        lefts = jnp.asarray(_load_npz_images({"images": blob["left"]}))
        rights = jnp.asarray(_load_npz_images({"images": blob["right"]}))
        frame_at = lambda i: (lefts[i % lefts.shape[0]][None],
                              rights[i % rights.shape[0]][None])
        left0, right0 = frame_at(0)
    else:
        s = max(cfg.model.image_size, 64)
        base = rng.normal(0, 1, (max(cfg.data.batch, 1), s, s, 3)).astype(
            np.float32)
        left0 = jnp.asarray(base)
        right0 = jnp.asarray(np.roll(base, -3, axis=2))
        frame_at = lambda i: (left0, right0)

    model = MODELS.build(cfg.model.name or "madnet", dtype=jnp.float32)
    params = model.init(jax.random.key(0), left0, right0)["params"]
    tx = optax.adam(cfg.train.lr)
    opt = tx.init(params)

    @jax.jit
    def step(params, opt, mask, left, right):
        def lf(p):
            out = model.apply({"params": p}, left, right)
            return photometric_loss(left, right, out["disparity"])
        loss, g = jax.value_and_grad(lf)(params)
        g = jax.tree.map(lambda gg, m: gg * m, g, mask)
        up, opt = tx.update(g, opt, params)
        # mask the UPDATE too: Adam's momentum would otherwise keep
        # moving deselected blocks for many frames after selection
        up = jax.tree.map(lambda u, m: u * m, up, mask)
        return optax.apply_updates(params, up), opt, loss

    sampler = MADSampler(list(params), sample_n=2, mode="probabilistic",
                         seed=cfg.train.seed)
    first = last = None
    for i in range(cfg.train.steps):
        selected = sampler.sample()
        mask = sampler.grad_mask(params, selected)
        fl, fr = frame_at(i)
        params, opt, loss = step(params, opt, mask, fl, fr)
        last = float(loss)
        sampler.update(selected, last)
        if first is None:
            first = last
        if i % max(cfg.train.steps // 5, 1) == 0:
            print(f"frame {i}: loss={last:.4f} blocks={selected}",
                  flush=True)
    if last is None:
        print("no steps run")
        return 1
    print(f"loss {first:.4f} -> {last:.4f}")
    print(f"task_metric photometric_online={last:.4f}")
    return 0 if np.isfinite(last) else 1


RUNNERS = {
    "segmentation": run_segmentation,
    "mae": run_mae,
    "supcon": run_supcon,
    "metric": run_metric,
    "keypoints": run_keypoints,
    "stereo": run_stereo,
    "stereo_online": run_stereo_online,
}


def main(argv=None) -> int:
    from deeplearning_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()   # step compiles are once-per-machine, not per-run
    from deeplearning_tpu.core.config import config_cli, pop_flag

    argv = list(sys.argv[1:] if argv is None else argv)
    task = pop_flag(argv, "--task")
    if task not in RUNNERS:
        raise SystemExit(f"--task must be one of {list(RUNNERS)}")
    cfg = config_cli(TaskConfig(), argv, description=__doc__)
    return RUNNERS[task](cfg)


if __name__ == "__main__":
    raise SystemExit(main())
