#!/usr/bin/env python
"""Standalone evaluation CLI — the per-project val.py / test.py successor.

  python tools/evaluate.py --model resnet18 --num-classes 10 \\
      --npz data.npz [--ckpt runs/x/ckpt/best] [--batch 64]

Runs the eval step over a dataset and prints top-1/top-5 plus per-class
accuracy from the confusion matrix (the reference's test.py writes a
results txt; here metrics go to stdout and optionally a json file).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", required=True)
    ap.add_argument("--num-classes", type=int, default=10)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--npz", default=None,
                    help="npz with model-ready 'images' and 'labels'")
    ap.add_argument("--folder", default=None,
                    help="ImageFolder root (real JPEG eval, val split)")
    ap.add_argument("--image-size", type=int, default=64)
    ap.add_argument("--val-rate", type=float, default=0.2)
    ap.add_argument("--seed", type=int, default=0,
                    help="split seed — MUST match train.seed for the "
                         "--folder val split to be truly held out")
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--out-json", default=None)
    ap.add_argument("--tta", action="store_true",
                    help="average probabilities over a horizontal flip "
                         "(yolov5 val --augment analog)")
    args = ap.parse_args(argv)
    if not args.npz and not args.folder:
        ap.error("one of --npz / --folder is required")

    from deeplearning_tpu.core.registry import MODELS
    from deeplearning_tpu.evaluation.metrics import (confusion_matrix,
                                                     miou_from_confusion,
                                                     topk_correct)

    if args.npz:
        blob = np.load(args.npz)
        images, labels = blob["images"], blob["labels"]

        def batches():
            bs = max(min(args.batch, len(images)), 1)
            n = (len(images) // bs) * bs
            for start in range(0, n, bs):
                yield (images[start:start + bs], labels[start:start + bs])
        sample = images[:1]
    else:
        # reuse the training-side loader stack (worker-pool decode,
        # clamped val batch) with the SAME split seed as training
        from deeplearning_tpu.data.build import (LoaderConfig,
                                                 build_classification_loaders)
        lcfg = LoaderConfig(global_batch=args.batch,
                            image_size=args.image_size,
                            val_rate=args.val_rate, seed=args.seed,
                            num_workers=args.workers)
        _, val_loader, class_to_idx = build_classification_loaders(
            args.folder, lcfg)
        if len(class_to_idx) != args.num_classes:
            ap.error(f"--num-classes {args.num_classes} but folder has "
                     f"{len(class_to_idx)} classes")
        if len(val_loader) == 0:
            raise SystemExit(
                "empty val split — raise --val-rate or add images")

        def batches():
            for batch in val_loader:
                yield (batch["image"], batch["label"])
        # init shape is fully determined by --image-size; no need to
        # decode a real batch just for model.init
        sample = np.zeros((1, args.image_size, args.image_size, 3),
                          np.float32)
    model = MODELS.build(args.model, num_classes=args.num_classes)
    variables = model.init(jax.random.key(0),
                           jnp.asarray(sample), train=False)
    if args.ckpt:
        from deeplearning_tpu.core.checkpoint import restore_variables
        variables = restore_variables(args.ckpt, variables)

    @jax.jit
    def eval_batch(imgs, labs):
        if args.tta:
            from deeplearning_tpu.ops.tta import classify_tta
            probs = classify_tta(
                lambda x: model.apply(variables, x, train=False), imgs)
            scores = jnp.log(jnp.maximum(probs, 1e-30))  # rank-equivalent
        else:
            scores = model.apply(variables, imgs, train=False)
        counts = topk_correct(scores, labs)
        cm = confusion_matrix(jnp.argmax(scores, -1), labs,
                              args.num_classes)
        return counts, cm

    totals = {"top1": 0, "top5": 0, "count": 0}
    cm_total = np.zeros((args.num_classes, args.num_classes), np.int64)
    for imgs, labs in batches():
        counts, cm = eval_batch(jnp.asarray(imgs), jnp.asarray(labs))
        for k in totals:
            totals[k] += int(counts[k])
        cm_total += np.asarray(cm)
    if totals["count"] == 0:
        raise SystemExit("no samples evaluated (empty dataset?)")

    count = totals["count"]
    stats = miou_from_confusion(cm_total)
    results = {
        "top1": totals["top1"] / count,
        "top5": totals["top5"] / count,
        "count": count,
        "per_class_acc": [round(float(a), 4)
                          for a in stats["class_acc"]],
    }
    print(json.dumps({k: (round(v, 4) if isinstance(v, float) else v)
                      for k, v in results.items()}))
    if args.out_json:
        with open(args.out_json, "w") as f:
            json.dump(results, f, indent=2)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
