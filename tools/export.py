#!/usr/bin/env python
"""Export CLI — the per-project export.py successor (yolov5 export.py
surface: one flag per backend).

  python tools/export.py --model vit_base_patch16_224 --num-classes 1000 \\
      --size 224 --format stablehlo --out model.shlo
  python tools/export.py --model resnet50 --format savedmodel --out sm/
  python tools/export.py --model mnist_cnn --channels 1 --size 28 \\
      --format onnx --out model.onnx
"""

from __future__ import annotations

import argparse
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
    ap.add_argument("--size", type=int, default=224)
    ap.add_argument("--channels", type=int, default=3)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--format",
                    choices=("stablehlo", "savedmodel", "onnx"),
                    default="stablehlo")
    ap.add_argument("--decode", action="store_true",
                    help="detectors: include the box decode in the graph "
                         "(pre-NMS raw detections, the yolov5 "
                         "export.py:29-159 export_detect / YOLOX "
                         "tools/export_onnx.py --decode analog)")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from deeplearning_tpu.core.checkpoint import load_pytree
    from deeplearning_tpu.core.registry import MODELS
    from deeplearning_tpu.export.serialize import (export_savedmodel,
                                                   export_stablehlo,
                                                   flops_estimate)

    build_kw = {}
    if args.format == "onnx":
        build_kw["dtype"] = jnp.float32   # portable f32 ONNX artifact
    model = MODELS.build(args.model, num_classes=args.num_classes,
                         **build_kw)
    example = jnp.zeros((args.batch, args.size, args.size, args.channels))
    variables = model.init(jax.random.key(0), example, train=False)
    if args.ckpt:
        restored = load_pytree(args.ckpt)
        params = restored.get("params", restored) \
            if isinstance(restored, dict) else restored
        variables = {**variables, "params": params}

    def fn(x):
        return model.apply(variables, x, train=False)

    if args.decode:
        hw = (args.size, args.size)
        if args.model.startswith("yolox"):
            from deeplearning_tpu.models.detection.yolox import (
                decode_outputs, yolox_grid)
            centers, strides = (jnp.asarray(a) for a in yolox_grid(hw))

            def fn(x):
                raw = model.apply(variables, x, train=False)
                return decode_outputs(raw, centers, strides)
        elif args.model.startswith("yolov5"):
            from deeplearning_tpu.models.detection.yolov5 import (
                decode_yolov5, yolov5_grid)
            grid = {k: jnp.asarray(v) for k, v in yolov5_grid(hw).items()}

            def fn(x):
                raw = model.apply(variables, x, train=False)
                return decode_yolov5(raw, grid)
        else:
            raise SystemExit(f"--decode not supported for {args.model!r} "
                             "(yolox*/yolov5* only)")

    print(f"model FLOPs (fwd, batch {args.batch}): "
          f"{flops_estimate(fn, example) / 1e9:.2f} G")
    if args.format == "onnx":
        from deeplearning_tpu.export.onnx import (export_onnx, load_onnx,
                                                  run_onnx)
        blob = export_onnx(fn, [example], args.out)
        # load-back numeric self-check, the export.py --simplify/check
        # analog (yolov5 export.py:43 onnx.checker + simplifier). A random
        # probe, not zeros: conv(0)=0 would mask a mis-serialized stem.
        probe = jnp.asarray(np.random.default_rng(0).normal(
            size=example.shape), jnp.float32)
        got = run_onnx(load_onnx(blob), np.asarray(probe))[0]
        want = np.asarray(fn(probe))
        err = float(np.abs(got - want).max())
        print(f"wrote {len(blob)} bytes of ONNX to {args.out}; "
              f"load-back max|diff|={err:.2e}")
        if err > 1e-3:
            print("ONNX self-check FAILED"); return 1
    elif args.format == "stablehlo":
        blob = export_stablehlo(fn, [example], args.out)
        print(f"wrote {len(blob)} bytes of StableHLO to {args.out}")
    else:
        ok = export_savedmodel(fn, [example], args.out)
        print(f"SavedModel written to {args.out}" if ok
              else "tensorflow unavailable")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
