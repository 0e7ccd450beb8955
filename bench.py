#!/usr/bin/env python
"""Headline benchmark: ViT-B/16 training throughput + MFU on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
value = ViT-B/16 training MFU (%). vs_baseline = MFU / 55 (the BASELINE.md
north-star target of >=55% MFU; >1.0 beats it). FLOPs are read from
XLA's compiled cost analysis — what the executable runs, fusion and
remat included — and the peak from the one table in
``deeplearning_tpu.utils.profiling``.

This measures the chip or nothing: where JAX finds no TPU it says so in
one line on stderr, prints no number and exits 1.
"""

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def main() -> int:
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"bench.py: no TPU — jax.devices()[0] is {device.platform!r} "
              f"({device.device_kind!r}); nothing measured", file=sys.stderr)
        return 1

    from deeplearning_tpu.core.compile_cache import enable_compile_cache
    from deeplearning_tpu.core.registry import MODELS
    from deeplearning_tpu.parallel.sharding import tree_bytes_per_device
    from deeplearning_tpu.train import TrainState, make_train_step
    from deeplearning_tpu.train.classification import make_loss_fn
    from deeplearning_tpu.train.optim import build_optimizer
    from deeplearning_tpu.train.schedules import build_schedule
    from deeplearning_tpu.utils.profiling import (cost_analysis_dict,
                                                  device_peak_flops)

    peak = device_peak_flops(device)     # unknown device_kind raises
    enable_compile_cache()

    batch = 128
    model = MODELS.build("vit_base_patch16_224", num_classes=1000)
    rng = jax.random.key(0)
    params = model.init(rng, jnp.zeros((1, 224, 224, 3)), train=False)["params"]
    sched = build_schedule("warmup_cosine", base_lr=1e-3, total_steps=10_000,
                           warmup_steps=100)
    tx = build_optimizer("adamw", sched, weight_decay=0.05, params=params)
    state = TrainState.create(apply_fn=model.apply, params=params, tx=tx)
    # per-device optimizer-state footprint: for this single-replica bench
    # it equals the global adamw mu/nu bytes; under
    # shard_state(zero1=True) it drops to ~1/dp
    opt_state_bytes = tree_bytes_per_device(state.opt_state)

    images = jnp.asarray(
        np.random.default_rng(0).normal(size=(batch, 224, 224, 3)),
        jnp.float32)
    labels = jnp.asarray(np.random.default_rng(1).integers(0, 1000, batch),
                         jnp.int32)
    data = {"image": images, "label": labels}

    step = make_train_step(make_loss_fn(label_smoothing=0.1), donate=True)
    compiled = jax.jit(
        lambda s, b, r: step(s, b, r), donate_argnums=(0,)
    ).lower(state, data, rng).compile()
    step_flops = float(cost_analysis_dict(compiled).get("flops", 0.0))
    if step_flops <= 0:
        raise RuntimeError("XLA cost analysis reported no FLOPs for the "
                           "train step; no MFU can be computed")

    # warmup (also materializes donation) then timed steps, driving the
    # compiled executable directly (step() has its own jit cache and
    # would pay a second identical compile)
    state, metrics = compiled(state, data, rng)
    jax.block_until_ready(metrics)
    n_steps = 20
    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, metrics = compiled(state, data, rng)
    jax.block_until_ready(metrics)
    dt = (time.perf_counter() - t0) / n_steps
    loss = float(metrics["loss"])
    if not np.isfinite(loss):
        raise FloatingPointError(f"non-finite loss {loss} after "
                                 f"{n_steps + 1} steps")

    mfu = step_flops / dt / peak * 100.0
    print(json.dumps({
        "metric": "vit_b16_train_mfu",
        "value": round(mfu, 2),
        "unit": "%",
        "vs_baseline": round(mfu / 55.0, 4),
        "images_per_sec": round(batch / dt, 1),
        "step_time_ms": round(dt * 1e3, 2),
        "platform": device.platform,
        "device": device.device_kind,
        "device_count": len(jax.devices()),
        "batch": batch,
        "opt_state_bytes_per_device": opt_state_bytes,
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
