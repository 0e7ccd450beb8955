#!/usr/bin/env python
"""One-shot TPU perf session: run EVERYTHING in a single process (a chip
belongs to one process at a time, and one process shares its compile
cache across stages; results print incrementally with flush so a run
cut by its time limit keeps what it finished).

Stages (ordered so the most important number lands first; every result
also appends to tools/mfu_results.jsonl):
  1. device probe (a tiny matmul must give the right answer)
  2. ViT-B/16 train-step MFU: naive vs XLA-SDPA vs flash_hb attention
  2b. round-4 numerics-delta isolation: erf-vs-tanh GELU on the ViT
      step, torch_pad-vs-SAME on a ResNet-50 step (VERDICT r4 #1 asked
      for the "asserted ~0" parity-fix cost to be measured)
  3. attention kernel microbench fwd+bwd at ViT + long-context shapes
  4. Swin-B window-attention: fused kernel vs lax path

Run: python tools/tpu_perf_session.py [--skip-train-steps]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


from bench_util import bench


def stage1_probe():
    t0 = time.perf_counter()
    x = jnp.ones((256, 256), jnp.bfloat16)
    val = float(jnp.asarray(x @ x, jnp.float32)[0, 0])
    assert val == 256.0, val
    print(f"[probe] ok in {time.perf_counter() - t0:.1f}s "
          f"device={jax.devices()[0].device_kind}", flush=True)


RESULTS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "mfu_results.jsonl")


def stage2_train_steps():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from perf_sweep import time_variant
    from deeplearning_tpu.ops.attention import flash_hb_adapter

    from deeplearning_tpu.ops.attention import sdpa_adapter

    results = {}
    for name, fn in [("naive", None),
                     ("sdpa", sdpa_adapter),
                     ("flash_hb", flash_hb_adapter)]:
        try:
            dt, mfu = time_variant(f"vit_train_{name}", 128, attn_fn=fn,
                                   results_path=RESULTS)
            results[name] = mfu
        except Exception as e:                       # noqa: BLE001
            print(f"[train:{name}] FAILED: {e}", flush=True)
    if results:
        best = max(results, key=results.get)
        print(f"[train] best attention for ViT-B/16 step: {best} "
              f"({results[best]:.2f}% MFU)", flush=True)
    return results


def stage2b_numerics_deltas():
    """Isolate the MFU cost of the round-4 parity fixes.

    erf-GELU: measure one ViT-B/16 train step under
    ``numerics.exact_numerics()`` (erf, the torch-parity flavor). Since
    round 5 the DEFAULT is the tanh approximation, so stage2's
    vit_train_naive row is the tanh baseline and this is the erf variant.
    First measured 2026-07-31: erf 47.94% vs tanh 51.71% MFU (−3.8 pts),
    which is why the default flipped.
    torch_pad: rebind the resnet module's torch_pad to XLA "SAME" for one
    ResNet-50 measurement (round 4 switched stride-2 convs to explicit
    torch-symmetric padding across resnet/yolox/hrnet/mobile/fpn).
    """
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from perf_sweep import time_variant
    from deeplearning_tpu.core import numerics
    from deeplearning_tpu.models.classification import resnet as resnet_mod

    try:
        with numerics.exact_numerics():
            time_variant("vit_train_gelu_erf", 128, results_path=RESULTS)
    except Exception as e:                           # noqa: BLE001
        print(f"[delta:gelu] FAILED: {e}", flush=True)

    orig_pad = resnet_mod.torch_pad
    try:
        time_variant("resnet50_train_torch_pad", 128,
                     model_name="resnet50", results_path=RESULTS)
        resnet_mod.torch_pad = lambda k, dilation=1: "SAME"
        time_variant("resnet50_train_same_pad", 128,
                     model_name="resnet50", results_path=RESULTS)
    except Exception as e:                           # noqa: BLE001
        print(f"[delta:pad] FAILED: {e}", flush=True)
    finally:
        resnet_mod.torch_pad = orig_pad


def stage3_attn_micro():
    from deeplearning_tpu.models.classification.vit import (
        dot_product_attention)
    from deeplearning_tpu.ops.pallas.flash_attention import (
        flash_attention, flash_attention_hb)

    def naive_bhnd(q, k, v):
        t = lambda x: x.transpose(0, 2, 1, 3)
        return t(dot_product_attention(t(q), t(k), t(v)))

    def jax_flash(q, k, v):
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as jf)
        return jf(q, k, v, sm_scale=q.shape[-1] ** -0.5)

    shapes = [(128, 12, 197, 64), (128, 16, 50, 80),
              (8, 12, 1024, 64), (2, 12, 4096, 64), (1, 12, 8192, 64)]
    variants = {"naive": naive_bhnd, "flash": flash_attention,
                "flash_hb": flash_attention_hb, "jax_flash": jax_flash}
    for shape in shapes:
        rng = np.random.default_rng(0)
        q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
                   for _ in range(3))
        for mode in ("fwd", "bwd"):
            row = {}
            for name, fn in variants.items():
                f = (jax.jit(fn) if mode == "fwd" else jax.jit(jax.grad(
                    lambda q, k, v, _fn=fn: _fn(q, k, v)
                    .astype(jnp.float32).sum(), argnums=(0, 1, 2))))
                try:
                    row[name] = bench(f, (q, k, v)) * 1e3
                except Exception as e:               # noqa: BLE001
                    print(f"[attn {shape} {mode} {name}] FAILED: {e}",
                          flush=True)
                    row[name] = float("nan")
            ok = {k: v for k, v in row.items() if not np.isnan(v)}
            best = min(ok, key=ok.get) if ok else "-"
            cells = " ".join(f"{k}={v:.3f}ms" for k, v in row.items())
            print(f"[attn {shape} {mode}] {cells} winner={best}",
                  flush=True)


def stage4_window():
    from deeplearning_tpu.ops.pallas.window_attention import (
        window_attention)

    # Swin-B stage-1 training shape: 224/4=56 → 64 windows of 7²=49
    # tokens, 4 heads d=32 (dim 128), batch 64 → BW=4096
    bw, n, heads, d = 64 * 64, 49, 4, 32
    rng = np.random.default_rng(0)
    qkv = jnp.asarray(rng.normal(size=(bw, n, 3, heads, d)), jnp.bfloat16)
    bias = jnp.asarray(rng.normal(size=(heads, n, n)), jnp.float32)

    def lax_path(qkv, bias):
        q = jnp.moveaxis(qkv[:, :, 0], 1, 2)
        k = jnp.moveaxis(qkv[:, :, 1], 1, 2)
        v = jnp.moveaxis(qkv[:, :, 2], 1, 2)
        s = jnp.einsum("bhnd,bhmd->bhnm", q * (d ** -0.5), k)
        s = s + bias[None].astype(s.dtype)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
        o = jnp.einsum("bhnm,bhmd->bhnd", p, v)
        return jnp.moveaxis(o, 1, 2).reshape(bw, n, heads * d)

    def fused(qkv, bias):
        return window_attention(qkv.reshape(bw, n, 3 * heads * d), bias,
                                heads=heads)

    variants = [("lax", lax_path), ("pallas", fused)]
    for name, fn in variants:
        try:
            dt = bench(jax.jit(fn), (qkv, bias)) * 1e3
            print(f"[window fwd {name}] {dt:.3f}ms", flush=True)
        except Exception as e:                       # noqa: BLE001
            print(f"[window fwd {name}] FAILED: {e}", flush=True)
    # training path: fwd+bwd through each variant
    for name, fn in variants:
        try:
            # grad w.r.t. qkv AND the trainable relative-position bias
            g = jax.jit(jax.grad(
                lambda qkv, bias, _f=fn: _f(qkv, bias)
                .astype(jnp.float32).sum(), argnums=(0, 1)))
            dt = bench(g, (qkv, bias)) * 1e3
            print(f"[window bwd {name}] {dt:.3f}ms", flush=True)
        except Exception as e:                       # noqa: BLE001
            print(f"[window bwd {name}] FAILED: {e}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-train-steps", action="store_true")
    ap.add_argument("--skip-micro", action="store_true")
    args = ap.parse_args()
    stage1_probe()
    # train-step MFU first: it is the headline number, so it must land
    # before the call's time limit can take the rest
    if not args.skip_train_steps:
        stage2_train_steps()
        stage2b_numerics_deltas()
    if not args.skip_micro:
        stage3_attn_micro()
        stage4_window()


if __name__ == "__main__":
    main()
