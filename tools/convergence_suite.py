#!/usr/bin/env python
"""Offline convergence suite on the HARD digits datasets (VERDICT r3 #5).

Generates the harder datasets if missing (100-class digit pairs with
clutter, 4k-scene detection, 3k-scene segmentation), then runs the
training CLIs sequentially — one per model family — appending one JSON
line per run to runs/convergence/results.jsonl and full stdout to
runs/convergence/<name>.log.

Run it in the background on the build box:
  mkdir -p runs/convergence && \\
    nohup python tools/convergence_suite.py > runs/convergence/suite.log 2>&1 &
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(ROOT, ".data", "digits")
OUT = os.path.join(ROOT, "runs", "convergence")

# a CPU suite: the children run one after another and never take a chip
ENV = dict(os.environ, JAX_PLATFORMS="cpu")

RUNS = [
    # (name, argv) — model families per VERDICT r3 #5 + the MoE curve.
    # ORDER = round-5 evidence priority: the working tree does not survive
    # between rounds, so rows whose numbers the README already cites run
    # first; historical r4 rows re-run last if wall-clock allows.
    # round-5 MoE closure (VERDICT r4 #3): the 56px 100-class run the
    # O(T²d) dense dispatch OOM-killed in r4 (rc=-9), now feasible with
    # the scatter/gather dispatch; dense twin = the equal-size baseline
    ("swin_moe_cls_hard56_v2", [
        "tools/train.py", "model.name=swin_moe_micro_patch2_window7",
        "model.num_classes=100", "model.precision=f32",
        f"data.npz={DATA}/cls_hard56/cls_hard.npz", "data.channels=3",
        "data.val_rate=0.1", "data.global_batch=64", "train.epochs=8",
        "optim.name=adamw", "optim.lr=0.002", "optim.warmup_steps=100",
        f"train.workdir={OUT}/swin_moe56"]),
    ("swin_dense_cls_hard56", [
        "tools/train.py", "model.name=swin_micro_patch2_window7",
        "model.num_classes=100", "model.precision=f32",
        f"data.npz={DATA}/cls_hard56/cls_hard.npz", "data.channels=3",
        "data.val_rate=0.1", "data.global_batch=64", "train.epochs=8",
        "optim.name=adamw", "optim.lr=0.002", "optim.warmup_steps=100",
        f"train.workdir={OUT}/swin_dense56"]),
    # round-5 two-stage plateau (VERDICT r4 #4): shrunk config for the
    # 1-core box — 96px, FrozenBN backbone stats, half-size proposal
    # stage — run to a plateau instead of the r4 80-step loss demo
    ("fasterrcnn_r18_plateau", [
        "tools/train_detection.py", "model.name=fasterrcnn_resnet18_fpn",
        "model.num_classes=10", "model.image_size=96",
        "model.backbone_frozen_bn=true",
        "model.rcnn_post_nms_top_n=128", "model.rcnn_roi_batch=64",
        f"data.coco={DATA}/det_hard/instances.json", "data.batch=8",
        "data.max_gt=8", "train.steps=700", "train.lr=0.0005"]),
    # round-5 matched-budget aug comparison (VERDICT r4 #2): plain vs
    # mosaic+random_perspective with the close-mosaic schedule (last 20%
    # of steps aug-free + YOLOX L1), both 2000 steps
    ("yolox_tiny_det_hard_2k", [
        "tools/train_detection.py", "model.name=yolox_tiny",
        "model.num_classes=10", "model.image_size=128",
        f"data.coco={DATA}/det_hard/instances.json", "data.batch=8",
        "data.max_gt=8", "train.steps=2000", "train.lr=0.001"]),
    ("yolox_tiny_det_hard_mosaic_close", [
        "tools/train_detection.py", "model.name=yolox_tiny",
        "model.num_classes=10", "model.image_size=128",
        f"data.coco={DATA}/det_hard/instances.json", "data.batch=8",
        "data.max_gt=8", "data.mosaic=true",
        "data.random_perspective=true", "data.degrees=5",
        "train.steps=2000", "train.no_aug_steps=400", "train.lr=0.001"]),
    # 28px/batch-16 keeps the dense dispatch einsum (O(T^2 d), an MXU
    # shape, brutal on one CPU core) small enough to converge offline
    ("swin_moe_cls_hard28_e10", [
        "tools/train.py", "model.name=swin_moe_micro_patch2_window7",
        "model.num_classes=100", "model.precision=f32",
        f"data.npz={DATA}/cls_hard28/cls_hard.npz", "data.channels=3",
        "data.val_rate=0.1", "data.global_batch=16", "train.epochs=10",
        "optim.name=adamw", "optim.lr=0.002", "optim.warmup_steps=100",
        f"train.workdir={OUT}/swin_moe"]),
    ("yolox_tiny_det_hard", [
        "tools/train_detection.py", "model.name=yolox_tiny",
        "model.num_classes=10", "model.image_size=128",
        f"data.coco={DATA}/det_hard/instances.json", "data.batch=8",
        "data.max_gt=8", "train.steps=700", "train.lr=0.001"]),
    ("yolox_tiny_det_hard_mosaic", [
        "tools/train_detection.py", "model.name=yolox_tiny",
        "model.num_classes=10", "model.image_size=128",
        f"data.coco={DATA}/det_hard/instances.json", "data.batch=8",
        "data.max_gt=8", "data.mosaic=true",
        "data.random_perspective=true", "data.degrees=5",
        "train.steps=500", "train.lr=0.001"]),
    ("retinanet_r18_det_hard", [
        "tools/train_detection.py", "model.name=retinanet_resnet18_fpn",
        "model.num_classes=10", "model.image_size=128",
        f"data.coco={DATA}/det_hard/instances.json", "data.batch=8",
        "data.max_gt=8", "train.steps=500", "train.lr=0.0005"]),
    ("resnet18_cls_hard", [
        "tools/train.py", "model.name=resnet18",
        "model.num_classes=100", "model.precision=f32",
        f"data.npz={DATA}/cls_hard/cls_hard.npz", "data.channels=3",
        "data.val_rate=0.1", "data.global_batch=32", "train.epochs=3",
        "optim.name=adamw", "optim.lr=0.001", "optim.warmup_steps=100",
        f"train.workdir={OUT}/resnet18"]),
    ("hrnet_w18_seg_hard", [
        "tools/train_task.py", "--task", "segmentation",
        "model.name=hrnet_w18_seg", "model.num_classes=11",
        f"data.npz={DATA}/seg_hard/seg_hard.npz", "data.batch=8",
        "train.steps=500", "train.lr=0.001"]),
    # two-stage demo: ~30 s/step on this box, so a short loss-curve run
    ("fasterrcnn_r18_short", [
        "tools/train_detection.py", "model.name=fasterrcnn_resnet18_fpn",
        "model.num_classes=10", "model.image_size=128",
        f"data.coco={DATA}/det_hard/instances.json", "data.batch=8",
        "data.max_gt=8", "train.steps=80", "train.lr=0.0005"]),
]


def ensure_datasets() -> None:
    from tools.make_digits import (make_cls_hard, make_det_hard,
                                   make_seg_hard)
    def npz_count(path):
        import numpy as np
        return len(np.load(path)["images"])

    def json_count(path):
        with open(path) as f:
            return len(json.load(f)["images"])

    jobs = [
        (f"{DATA}/cls_hard/cls_hard.npz", npz_count, 12000,
         lambda: make_cls_hard(f"{DATA}/cls_hard", n_images=12000)),
        (f"{DATA}/cls_hard28/cls_hard.npz", npz_count, 4000,
         lambda: make_cls_hard(f"{DATA}/cls_hard28", n_images=4000,
                               size=28, seed=2)),
        (f"{DATA}/cls_hard56/cls_hard.npz", npz_count, 8000,
         lambda: make_cls_hard(f"{DATA}/cls_hard56", n_images=8000,
                               size=56, seed=4)),
        (f"{DATA}/det_hard/instances.json", json_count, 4000,
         lambda: make_det_hard(f"{DATA}/det_hard", n_images=4000)),
        (f"{DATA}/seg_hard/seg_hard.npz", npz_count, 3000,
         lambda: make_seg_hard(f"{DATA}/seg_hard", n_images=3000)),
    ]
    for path, count, want, make in jobs:
        # size check, not just existence: a dataset generated earlier
        # with different parameters would silently skew the results
        if os.path.exists(path) and count(path) == want:
            print(f"dataset ok: {path}")
        else:
            t0 = time.time()
            make()
            print(f"generated {path} in {time.time() - t0:.0f}s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated run-name substrings")
    ap.add_argument("--timeout", type=float, default=7200,
                    help="per-run wall clock cap (s)")
    args = ap.parse_args(argv)
    os.makedirs(OUT, exist_ok=True)
    sys.path.insert(0, ROOT)
    ensure_datasets()

    results_path = os.path.join(OUT, "results.jsonl")
    done = set()
    if os.path.exists(results_path):
        with open(results_path) as f:
            done = {e["name"] for e in map(json.loads, f)
                    if isinstance(e, dict) and e.get("rc") == 0}
    for name, cmd in RUNS:
        if args.only and not any(tok in name
                                 for tok in args.only.split(",")):
            continue
        if name in done:
            print(f"skip {name} (already in results.jsonl)")
            continue
        log_path = os.path.join(OUT, f"{name}.log")
        print(f"=== {name}: {' '.join(cmd)}")
        t0 = time.time()
        with open(log_path, "w") as log:
            try:
                rc = subprocess.run(
                    [sys.executable] + cmd, cwd=ROOT, env=ENV,
                    stdout=log, stderr=subprocess.STDOUT,
                    timeout=args.timeout).returncode
            except subprocess.TimeoutExpired:
                rc = -9
        tail = ""
        try:
            with open(log_path) as f:
                lines = [l.strip() for l in f.read().splitlines()
                         if l.strip()]
            tail = lines[-1] if lines else ""
        except OSError:
            pass
        entry = {"name": name, "rc": rc,
                 "minutes": round((time.time() - t0) / 60, 1),
                 "final": tail, "cmd": " ".join(cmd)}
        with open(results_path, "a") as f:
            f.write(json.dumps(entry) + "\n")
        print(json.dumps(entry))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
