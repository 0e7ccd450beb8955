#!/usr/bin/env python
"""Detection demo CLI: image in → annotated image out.

  python tools/demo.py --model yolox_tiny --num-classes 80 \\
      --input street.jpg --out street_det.jpg [--ckpt DIR] [--tta]

The YOLOX ``tools/demo.py`` / yolov5 ``detect.py`` successor: builds any
registry detector, restores a checkpoint, runs the family's fixed-shape
postprocess (optionally multi-scale+flip TTA for the YOLOX family),
draws the surviving boxes with ``utils/visualize.draw_boxes`` and writes
the annotated image. Detections also print as JSON lines for scripting.
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
    ap.add_argument("--model", required=True,
                    help="registry name (yolox_*, yolov5*, retinanet_*, "
                         "fcos_*, fasterrcnn_*)")
    ap.add_argument("--num-classes", type=int, default=80)
    ap.add_argument("--ckpt", default=None,
                    help="orbax checkpoint dir (TrainState or params)")
    ap.add_argument("--input", required=True, help="image file")
    ap.add_argument("--out", default=None,
                    help="annotated image path (default <input>_det.png)")
    ap.add_argument("--size", type=int, default=640)
    ap.add_argument("--score", type=float, default=0.3)
    ap.add_argument("--tta", action="store_true",
                    help="multi-scale+flip TTA (YOLOX family only)")
    ap.add_argument("--classes", default=None,
                    help="json mapping class index -> name")
    args = ap.parse_args(argv)

    from deeplearning_tpu.core.checkpoint import restore_variables
    from deeplearning_tpu.core.registry import MODELS
    from deeplearning_tpu.data.datasets import load_image
    from deeplearning_tpu.utils.visualize import draw_boxes
    from train_detection import build_task

    # fasterrcnn heads train with class 0 = background (train_detection
    # builds them with num_classes+1 and the postprocess shifts labels)
    model_classes = args.num_classes + (
        1 if args.model.startswith("fasterrcnn") else 0)
    model = MODELS.build(args.model, num_classes=model_classes)
    is_npy = args.input.lower().endswith(".npy")
    raw = np.asarray(load_image(args.input), np.float32)  # (H, W, 3)
    h0, w0 = raw.shape[:2]
    if not is_npy:               # image files decode to 0-255
        raw = raw / 255.0        # .npy is model-ready by convention
    elif raw.max() > 4.0:
        # mean/std-normalized arrays top out near ~3; values beyond
        # that mean raw 0-255 pixels were saved un-normalized
        print(f"warning: .npy input has max {raw.max():.1f} — looks "
              "like raw 0-255 pixels; .npy must be model-ready "
              "(normalized) or detections will be garbage",
              file=sys.stderr)
    images = jax.image.resize(jnp.asarray(raw),
                              (args.size, args.size, 3), "bilinear")[None]

    variables = model.init(jax.random.key(0), images, train=False)
    if args.ckpt:
        variables = restore_variables(args.ckpt, variables)
    params = variables["params"]
    stats = variables.get("batch_stats", {})

    if args.tta:
        if not args.model.startswith("yolox"):
            raise SystemExit("--tta currently supports the YOLOX family")
        from deeplearning_tpu.ops.tta import yolox_tta
        raw_fn = lambda x: model.apply(
            {"params": params, "batch_stats": stats}, x, train=False)
        det = jax.jit(lambda im: yolox_tta(
            raw_fn, im, score_thresh=args.score, max_det=100))(images)
    else:
        _, predict_fn = build_task(model, args.model, args.num_classes,
                                   score_thresh=args.score, max_det=100)
        det = jax.jit(predict_fn)(params, stats, images)

    keep = np.asarray(det["valid"][0])
    boxes = np.asarray(det["boxes"][0])[keep]
    scores = np.asarray(det["scores"][0])[keep]
    labels = np.asarray(det["labels"][0])[keep]
    # back to the original frame
    boxes = boxes * np.array([w0 / args.size, h0 / args.size] * 2)

    names = {}
    if args.classes:
        with open(args.classes) as f:
            names = {int(k): v for k, v in json.load(f).items()}
    for b, s, c in zip(boxes, scores, labels):
        print(json.dumps({
            "box": [round(float(x), 1) for x in b],
            "score": round(float(s), 4),
            "label": names.get(int(c), int(c))}))

    # render: image files are 0-1 here; arbitrary-range .npy is min-max
    # normalized for display only
    disp = raw if not is_npy else \
        (raw - raw.min()) / max(raw.max() - raw.min(), 1e-6)
    annotated = draw_boxes(
        np.clip(disp * 255.0, 0, 255).astype(np.uint8), boxes,
        labels=[names.get(int(c), str(int(c))) for c in labels],
        scores=scores)
    out_path = args.out or os.path.splitext(args.input)[0] + "_det.png"
    from PIL import Image
    Image.fromarray(annotated).save(out_path)
    print(f"wrote {out_path} ({keep.sum()} detections)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
