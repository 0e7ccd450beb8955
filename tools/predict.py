#!/usr/bin/env python
"""Inference CLI — the per-project predict.py successor.

  python tools/predict.py --model mnist_cnn --ckpt runs/x/ckpt/best \\
      --input img.png [--classes class_indices.json] [--topk 5]

A thin client of ``deeplearning_tpu.serve.InferenceEngine``: ONE code
path builds the session (params restored once, EMA-preferring), AOT-
compiles exactly the bucket the input needs, and runs the jitted
forward — plain softmax, flip-TTA (``--tta``), or a detection family's
fixed-shape postprocess — with results reported PER IMAGE. Multi-image
``.npz`` batches print one line per image; detection output prints only
the valid rows (the class −1 padding slots of the fixed-shape outputs
are engine-internal and never shown). Serving the same session under
concurrent load is ``tools/serve.py``; this is the one-shot surface.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import numpy as np


def load_batch(path: str, size: int, task: str = "classify") -> np.ndarray:
    """Image files go through the eval transform (resize+/255 for
    detection, demo.py's frame); .npz batches are MODEL-READY by
    convention (tools/train.py feeds npz arrays raw), so they bypass
    normalization — mixing the two would double-normalize."""
    from deeplearning_tpu.data.datasets import load_image
    if path.endswith(".npz"):
        return np.asarray(np.load(path)["images"], np.float32)
    raw = np.asarray(load_image(path), np.float32)
    if task == "detect":
        import jax.numpy as jnp
        if not path.lower().endswith(".npy"):
            raw = raw / 255.0        # .npy is model-ready by convention
        return np.asarray(jax.image.resize(
            jnp.asarray(raw), (size, size, 3), "bilinear"))[None]
    from deeplearning_tpu.data.transforms import (
        classification_eval_transform)
    fn = classification_eval_transform((size, size))
    return fn({"image": raw[None]})["image"]


def report_classification(probs: np.ndarray, names, topk: int) -> None:
    for bi, p in enumerate(probs):
        order = np.argsort(-p)[:topk]
        print(f"image {bi}: " + "  ".join(
            f"{names.get(int(i), int(i))}={p[i]:.4f}" for i in order))


def report_detections(det, names) -> None:
    """Per-image detection lines, VALID rows only — the fixed-shape
    padding rows (class −1 by the PR 3 convention) stay internal."""
    for bi in range(det["boxes"].shape[0]):
        keep = np.asarray(det["valid"][bi], bool)
        rows = [{"box": [round(float(x), 1) for x in b],
                 "score": round(float(s), 4),
                 "label": names.get(int(c), int(c))}
                for b, s, c in zip(np.asarray(det["boxes"][bi])[keep],
                                   np.asarray(det["scores"][bi])[keep],
                                   np.asarray(det["labels"][bi])[keep])]
        print(f"image {bi}: " + json.dumps(rows))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", required=True)
    ap.add_argument("--num-classes", type=int, default=10)
    ap.add_argument("--ckpt", default=None,
                    help="orbax checkpoint dir (step dir or 'best')")
    ap.add_argument("--input", required=True)
    ap.add_argument("--size", type=int, default=224)
    ap.add_argument("--topk", type=int, default=5)
    ap.add_argument("--classes", default=None,
                    help="json mapping class index -> name")
    ap.add_argument("--tta", action="store_true",
                    help="average probabilities over a horizontal-flip "
                         "view (yolov5 --augment analog)")
    ap.add_argument("--score", type=float, default=0.3,
                    help="detection score threshold")
    ap.add_argument("--max-det", type=int, default=100)
    ap.add_argument("--nms-impl", default="auto")
    args = ap.parse_args(argv)

    from deeplearning_tpu.models.detection.predict import (
        is_detection_model)
    from deeplearning_tpu.serve import InferenceEngine

    task = "detect" if is_detection_model(args.model) else "classify"
    images = load_batch(args.input, args.size, task)
    n = images.shape[0]
    if args.input.endswith(".npz"):
        # npz batches are model-ready at THEIR OWN resolution — the
        # engine buckets compile for the actual array shape, not --size
        if images.shape[1] != images.shape[2]:
            raise SystemExit(f"npz images must be square for the "
                             f"bucketed engine, got {images.shape}")
        args.size = images.shape[1]
    # one-shot CLI: compile exactly the bucket this input needs (plus
    # bucket 1 so the engine surface stays uniform), nothing speculative
    engine = InferenceEngine(
        args.model, num_classes=args.num_classes, ckpt=args.ckpt,
        image_size=args.size, batch_buckets=sorted({1, n}),
        tta=args.tta, score_thresh=args.score, max_det=args.max_det,
        nms_impl=args.nms_impl)

    names = {}
    if args.classes:
        with open(args.classes) as f:
            names = {int(k): v for k, v in json.load(f).items()}

    out = engine.infer(images)
    if engine.task == "detect":
        report_detections(out, names)
    else:
        report_classification(out, names, args.topk)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
