#!/usr/bin/env python
"""Swin window-attention microbenchmark on the real chip.

Times the lax reference path vs the fused Pallas window kernel at
Swin-T/B production shapes (the unit_test.py speed-comparison analog for
classification/swin_transformer/kernels/window_process). Also times a
full swin_tiny forward with use_pallas on/off. Appends JSON lines to
tools/window_results.jsonl; run it as one process, the only one on the
chip."""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


from bench_util import bench


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--wpb", type=int, default=8, help="windows per block")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, kernel-only (CPU interpret check)")
    args = ap.parse_args()

    from deeplearning_tpu.ops.pallas.window_attention import window_attention
    from deeplearning_tpu.ops.window_utils import windowed_attention_reference

    results = []
    # (BW, N, heads, d): Swin-T stage1 (56x56/7 -> 64 win) batch 128;
    # Swin-B stage3 shapes; window 7 -> N=49
    SHAPES = [
        (128 * 64, 49, 3, 32),    # swin-T stage 1, batch 128
        (128 * 16, 49, 6, 32),    # stage 2
        (128 * 4, 49, 12, 32),    # stage 3
        (64 * 64, 49, 4, 32),     # swin-B stage 1, batch 64
        (64 * 4, 49, 16, 32),     # swin-B stage 3
    ]
    if args.smoke:
        SHAPES = [(16, 49, 3, 32)]
    rng = np.random.default_rng(0)
    for bw, n, heads, d in SHAPES:
        qkv = jnp.asarray(rng.normal(size=(bw, n, 3, heads, d)),
                          jnp.bfloat16)
        bias = jnp.asarray(rng.normal(size=(heads, n, n)), jnp.float32)
        f_ref = jax.jit(lambda q, b: windowed_attention_reference(q, b, None))
        f_pal = jax.jit(lambda q, b: window_attention(
            q, b, windows_per_block=args.wpb))
        t_ref = bench(f_ref, (qkv, bias))
        t_pal = bench(f_pal, (qkv, bias))
        rec = {"shape": [bw, n, heads, d], "lax_ms": round(t_ref * 1e3, 3),
               "pallas_ms": round(t_pal * 1e3, 3),
               "speedup": round(t_ref / t_pal, 3), "wpb": args.wpb}
        results.append(rec)
        print(json.dumps(rec), flush=True)

    if args.smoke:
        return
    # full model: swin_tiny forward, pallas on/off
    from deeplearning_tpu.core.registry import MODELS
    x = jnp.asarray(rng.normal(size=(64, 224, 224, 3)), jnp.float32)
    for use_pallas in (False, True):
        model = MODELS.build("swin_tiny_patch4_window7_224",
                             num_classes=1000, use_pallas=use_pallas)
        params = model.init(jax.random.key(0), jnp.zeros((1, 224, 224, 3)),
                            train=False)["params"]
        f = jax.jit(lambda p, x: model.apply({"params": p}, x, train=False))
        t = bench(f, (params, x), n=10)
        rec = {"model": "swin_tiny", "use_pallas": use_pallas,
               "fwd_ms": round(t * 1e3, 2),
               "img_per_s": round(64 / t, 1)}
        results.append(rec)
        print(json.dumps(rec), flush=True)

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "window_results.jsonl"), "a") as f:
        for rec in results:
            f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
