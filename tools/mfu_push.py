#!/usr/bin/env python
"""MFU push experiments: ViT-B/16 train-step variants, one per invocation.

Each run measures ONE variant to completion and appends a JSON line to
tools/mfu_results.jsonl. Variants are selected by CLI flags so that
XLA-flag experiments (which must be set before backend init) get a fresh
interpreter. Run variants SEQUENTIALLY: a chip belongs to one process
at a time, so a second invocation started while the first holds the
device fails or hangs.

Usage:
  python tools/mfu_push.py --attn naive
  python tools/mfu_push.py --attn flash_hb --head-block 4
  XLA_FLAGS="--xla_tpu_enable_latency_hiding_scheduler=true" \
      python tools/mfu_push.py --attn naive --tag lhs
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import bench_util  # noqa: F401  (side effect: persistent compile cache)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--attn", default="naive",
                    choices=["naive", "flash", "flash_hb", "sdpa"])
    ap.add_argument("--head-block", type=int, default=4)
    ap.add_argument("--block-q", type=int, default=128)
    ap.add_argument("--block-k", type=int, default=128)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    from deeplearning_tpu.utils.profiling import device_peak_flops
    peak = device_peak_flops()       # an unknown device raises up front

    from deeplearning_tpu.core.registry import MODELS
    from deeplearning_tpu.train import TrainState, make_train_step
    from deeplearning_tpu.train.classification import make_loss_fn
    from deeplearning_tpu.train.optim import build_optimizer
    from deeplearning_tpu.train.schedules import build_schedule

    attn_fn = None
    if args.attn == "sdpa":
        from deeplearning_tpu.ops.attention import sdpa_adapter
        attn_fn = sdpa_adapter
    elif args.attn == "flash":
        from deeplearning_tpu.ops.attention import flash_attn_adapter
        attn_fn = flash_attn_adapter
    elif args.attn == "flash_hb":
        from deeplearning_tpu.ops.pallas.flash_attention import (
            flash_attention_hb)

        def attn_fn(q, k, v, dropout_rate=0.0, deterministic=True, rng=None):
            t = lambda x: x.transpose(0, 2, 1, 3)
            return t(flash_attention_hb(
                t(q), t(k), t(v), head_block=args.head_block,
                block_q=args.block_q, block_k=args.block_k))

    model = MODELS.build("vit_base_patch16_224", num_classes=1000,
                         remat=args.remat, attn_fn=attn_fn)
    rng = jax.random.key(0)
    params = model.init(rng, jnp.zeros((1, 224, 224, 3)),
                        train=False)["params"]
    sched = build_schedule("warmup_cosine", base_lr=1e-3, total_steps=10_000,
                           warmup_steps=100)
    tx = build_optimizer("adamw", sched, weight_decay=0.05, params=params)
    state = TrainState.create(apply_fn=model.apply, params=params, tx=tx)

    batch = args.batch
    images = jnp.asarray(np.random.default_rng(0).normal(
        size=(batch, 224, 224, 3)), jnp.float32)
    labels = jnp.asarray(np.random.default_rng(1).integers(0, 1000, batch),
                         jnp.int32)
    data = {"image": images, "label": labels}

    step = make_train_step(make_loss_fn(label_smoothing=0.1), donate=True)
    t_c0 = time.perf_counter()
    compiled = jax.jit(lambda s, b, r: step(s, b, r),
                       donate_argnums=(0,)).lower(state, data, rng).compile()
    compile_s = time.perf_counter() - t_c0
    cost = compiled.cost_analysis()
    step_flops = float(cost.get("flops", 0.0)) if cost else 0.0

    # drive the compiled executable directly — step() has its own jit
    # cache and would pay a second identical compile
    state, metrics = compiled(state, data, rng)
    loss0 = float(metrics["loss"])  # D2H sync; also a sanity check
    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, metrics = compiled(state, data, rng)
    loss1 = float(metrics["loss"])
    dt = (time.perf_counter() - t0) / args.steps

    # Pallas custom calls are opaque to XLA cost analysis, so for non-naive
    # attention `step_flops` undercounts. mfu_ref uses the naive-path
    # compiled FLOPs for THIS model/image config, scaled by batch, so
    # variants compare on the same semantic workload. The per-image value
    # is measured by the naive non-remat run and cached in a sidecar keyed
    # by config, so it can't silently go stale when the config changes.
    # batch is part of the key: XLA's compiled FLOPs per image differ by
    # ~11% between batch 128 and 512 (fusion decisions), so a batch-free
    # key would let the last naive run poison other batches' mfu_ref_pct
    ref_key = f"vit_base_patch16_224/img224/b{batch}"
    ref_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "mfu_ref_flops.json")
    ref_cache = {}
    if os.path.exists(ref_path):
        with open(ref_path) as f:
            ref_cache = json.load(f)
    if args.attn == "naive" and not args.remat and step_flops > 0:
        ref_cache[ref_key] = step_flops / batch
        with open(ref_path, "w") as f:
            json.dump(ref_cache, f)
    if ref_key in ref_cache:
        ref_flops = ref_cache[ref_key] * batch
    else:  # no naive run measured yet on this machine
        ref_flops = 1.3543e13 * batch / 128.0  # batch-128 measurement, r2
    from bench_util import append_result
    extra = {
        "attn": args.attn,
        "remat": args.remat,
        "head_block": args.head_block if args.attn == "flash_hb" else None,
        "mfu_ref_pct": round(ref_flops / dt / peak * 100.0, 2),
        "compile_s": round(compile_s, 1),
        "flops_per_step": step_flops,
        "loss0": round(loss0, 4), "loss1": round(loss1, 4),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
    }
    rec = append_result(
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "mfu_results.jsonl"),
        args.tag or args.attn, batch=batch, step_ms=dt * 1e3,
        img_per_s=batch / dt,
        mfu_pct=step_flops / dt / peak * 100.0, **extra)
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
