#!/usr/bin/env python
"""ViT-B/16 training-step perf sweep on one TPU chip.

Times the real train step (same construction as bench.py) across
variants — batch size, attention softmax dtype, Pallas flash kernel —
and prints a table of step-time / images-per-sec / MFU per variant.
MFU uses XLA's compiled cost analysis like bench.py so numbers are
comparable. Run on the real chip: `python tools/perf_sweep.py`.
"""

import argparse
import contextlib
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

import bench_util  # noqa: F401  (side effect: persistent compile cache)

from deeplearning_tpu.utils.profiling import (cost_analysis_dict,
                                              device_peak_flops)


def bf16_softmax_attention(q, k, v, dropout_rate=0.0, deterministic=True,
                           rng=None):
    """Naive attention with softmax kept in bf16 (row max still exact)."""
    del dropout_rate, deterministic, rng
    scale = q.shape[-1] ** -0.5
    attn = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
    attn = jax.nn.softmax(attn, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", attn, v)


class _ConvPatchEmbed(nn.Module):
    """ViT's ORIGINAL strided-conv patch embed.

    Since round 5 `vit.PatchEmbed` lowers the patch conv as reshape+matmul
    (measured +1.2 MFU points); this restores the conv lowering so the
    A/B in ``--set r5`` stays reproducible."""
    patch_size: int = 16
    embed_dim: int = 768
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        x = nn.Conv(self.embed_dim, (self.patch_size, self.patch_size),
                    strides=(self.patch_size, self.patch_size),
                    dtype=self.dtype, name="proj")(x)
        b, h, w, c = x.shape
        return x.reshape(b, h * w, c)


@contextlib.contextmanager
def patch_embed_as_conv():
    """Swap ViT back to the conv patch-embed lowering (the pre-r5 path)."""
    from deeplearning_tpu.models.classification import vit as vit_mod
    orig = vit_mod.PatchEmbed
    vit_mod.PatchEmbed = _ConvPatchEmbed
    try:
        yield
    finally:
        vit_mod.PatchEmbed = orig


def time_variant(name, batch, attn_fn=None, remat=False, n_steps=20,
                 model_name="vit_base_patch16_224", image_size=224,
                 results_path=None):
    peak = device_peak_flops()       # an unknown device raises up front
    from deeplearning_tpu.core.registry import MODELS
    from deeplearning_tpu.train import TrainState, make_train_step
    from deeplearning_tpu.train.classification import make_loss_fn
    from deeplearning_tpu.train.optim import build_optimizer
    from deeplearning_tpu.train.schedules import build_schedule

    kw = {"num_classes": 1000}
    if model_name.startswith("vit"):
        kw.update(attn_fn=attn_fn, remat=remat)
    model = MODELS.build(model_name, **kw)
    rng = jax.random.key(0)
    variables = model.init(rng, jnp.zeros((1, image_size, image_size, 3)),
                           train=False)
    params = variables["params"]
    batch_stats = variables.get("batch_stats")
    sched = build_schedule("warmup_cosine", base_lr=1e-3,
                           total_steps=10_000, warmup_steps=100)
    tx = build_optimizer("adamw", sched, weight_decay=0.05, params=params)
    state = TrainState.create(apply_fn=model.apply, params=params, tx=tx,
                              batch_stats=batch_stats)
    images = jnp.asarray(
        np.random.default_rng(0).normal(
            size=(batch, image_size, image_size, 3)), jnp.float32)
    labels = jnp.asarray(
        np.random.default_rng(1).integers(0, 1000, batch), jnp.int32)
    data = {"image": images, "label": labels}
    step = make_train_step(
        make_loss_fn(label_smoothing=0.1,
                     has_batch_stats=batch_stats is not None),
        donate=True)
    compiled = jax.jit(lambda s, b, r: step(s, b, r),
                       donate_argnums=(0,)).lower(state, data,
                                                  rng).compile()
    step_flops = float(cost_analysis_dict(compiled).get("flops", 0.0))

    # drive the ALREADY-compiled executable (re-calling step would pay a
    # second identical XLA compile, minutes on TPU)
    state, metrics = compiled(state, data, rng)
    jax.block_until_ready(metrics)
    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, metrics = compiled(state, data, rng)
    jax.block_until_ready(metrics)
    dt = (time.perf_counter() - t0) / n_steps
    mfu = step_flops / dt / peak * 100.0
    # per-step-synced tail stats: the pipelined mean above hides stalls
    # (a stalled iteration, host jitter); p50/p90 make regressions visible
    per_step = []
    for _ in range(min(n_steps, 10)):
        t1 = time.perf_counter()
        state, metrics = compiled(state, data, rng)
        jax.block_until_ready(metrics)
        per_step.append(time.perf_counter() - t1)
    p50, p90 = np.percentile(per_step, [50, 90])
    print(f"{name:40s} batch={batch:4d} step={dt * 1e3:8.2f}ms "
          f"img/s={batch / dt:8.1f} mfu={mfu:6.2f}% "
          f"p50={p50 * 1e3:7.2f}ms p90={p90 * 1e3:7.2f}ms", flush=True)
    if results_path:
        from bench_util import append_result
        append_result(results_path, name, batch=batch, step_ms=dt * 1e3,
                      img_per_s=batch / dt, mfu_pct=mfu, model=model_name,
                      step_ms_p50=round(p50 * 1e3, 2),
                      step_ms_p90=round(p90 * 1e3, 2))
    del state, compiled, step
    return dt, mfu


def time_feed_variant(name, batch, n_steps=20, depth=2,
                      model_name="vit_base_patch16_224", image_size=224,
                      results_path=None):
    """End-to-end FEED benchmark: the jitted step driven through the
    Trainer's pipelined throughput pass over REAL loader batches, wrapped
    (depth>0) or not (depth=0) in a DevicePrefetcher. Unlike
    ``time_variant`` (one resident device batch, pure step time), every
    iteration here pays decode + host→HBM transfer — the row's
    ``h2d_wait_frac`` / ``prefetch_occupancy`` columns show how much of
    it the prefetch pipeline hides, so an on-chip A/B of
    feed_prefetch vs feed_serial attributes the MFU delta directly."""
    import numpy as np

    from bench_util import feed_stats
    peak = device_peak_flops()       # an unknown device raises up front
    from deeplearning_tpu.core.registry import MODELS
    from deeplearning_tpu.data import ArraySource, DataLoader
    from deeplearning_tpu.train import TrainState, make_train_step
    from deeplearning_tpu.train.classification import make_loss_fn
    from deeplearning_tpu.train.optim import build_optimizer
    from deeplearning_tpu.train.schedules import build_schedule
    from deeplearning_tpu.train.trainer import Trainer

    model = MODELS.build(model_name, num_classes=1000)
    variables = model.init(jax.random.key(0),
                           jnp.zeros((1, image_size, image_size, 3)),
                           train=False)
    params = variables["params"]
    sched = build_schedule("warmup_cosine", base_lr=1e-3,
                           total_steps=10_000, warmup_steps=100)
    tx = build_optimizer("adamw", sched, weight_decay=0.05, params=params)
    state = TrainState.create(apply_fn=model.apply, params=params, tx=tx,
                              batch_stats=variables.get("batch_stats"))
    rng = np.random.default_rng(0)
    n_data = batch * 4          # enough distinct batches to cycle
    images = rng.normal(size=(n_data, image_size, image_size, 3)
                        ).astype(np.float32)
    labels = rng.integers(0, 1000, n_data).astype(np.int32)
    loader = DataLoader(ArraySource(image=images, label=labels),
                        global_batch=batch, shuffle=False)
    step = make_train_step(
        make_loss_fn(label_smoothing=0.1,
                     has_batch_stats=variables.get("batch_stats")
                     is not None),
        donate=True, donate_batch=True)
    trainer = Trainer(state=state, train_step=step, train_loader=loader,
                      retrace_warn=False,
                      prefetch=depth if depth else 0)
    aot = trainer.precompile()   # AOT warmup overlapped with feed start
    flops = 0.0
    if getattr(trainer, "_aot_step", None) is not None:
        flops = float(cost_analysis_dict(trainer._aot_step
                                         ).get("flops", 0.0))
    ips = trainer.throughput(n_iters=n_steps)
    stats = trainer.throughput_stats
    dt = stats["step_ms_mean"] / 1e3
    mfu = flops / dt / peak * 100.0 if flops else 0.0
    feed = feed_stats(stats)
    print(f"{name:40s} batch={batch:4d} step={dt * 1e3:8.2f}ms "
          f"img/s={ips:8.1f} mfu={mfu:6.2f}% "
          f"h2d_frac={feed.get('h2d_wait_frac', 0.0):6.3f} "
          f"occ={feed.get('prefetch_occupancy', 0.0):4.1f} "
          f"aot={0.0 if aot is None else aot:6.2f}s", flush=True)
    if results_path:
        from bench_util import append_result
        append_result(results_path, name, batch=batch, step_ms=dt * 1e3,
                      img_per_s=ips, mfu_pct=mfu, model=model_name,
                      step_ms_p50=round(stats["step_ms_p50"], 2),
                      step_ms_p90=round(stats["step_ms_p90"], 2),
                      **feed)
    return dt, mfu


def _detect_nms_case(rng, n):
    ctr = rng.uniform(0, 2000, (n, 2))
    wh = rng.uniform(4, 64, (n, 2))
    boxes = np.concatenate([ctr - wh / 2, ctr + wh / 2],
                           axis=-1).astype(np.float32)
    return jnp.asarray(boxes), jnp.asarray(
        rng.uniform(0, 1, n).astype(np.float32))


def time_detect_set(results_path=None):
    """Detection postprocess sweep (ops/nms.py + ops/roi_align.py).

    Op rows: greedy vs blocked NMS (plus the Pallas tile kernel on TPU)
    at N in {2k, 20k}; one-pass vs masked multiscale RoIAlign at R in
    {256, 1k}. End-to-end row: the jitted RetinaNet eval path (forward +
    decode + blocked NMS), i.e. exactly what one eval step runs."""
    import functools

    from bench_util import append_op_result, append_result, bench
    from deeplearning_tpu.ops import nms as nms_ops
    from deeplearning_tpu.ops import roi_align as roi_ops

    rng = np.random.default_rng(0)
    impls = ["greedy", "blocked"]
    if jax.default_backend() == "tpu":
        impls.append("pallas")
    for n in (2000, 20000):
        boxes, scores = _detect_nms_case(rng, n)
        for impl in impls:
            fn = jax.jit(functools.partial(
                nms_ops.nms, iou_threshold=0.5, max_out=100, impl=impl))
            ms = bench(fn, (boxes, scores), n=10) * 1e3
            print(f"nms_{impl:8s} n={n:6d} {ms:9.3f} ms", flush=True)
            if results_path:
                append_op_result(results_path, f"nms_{impl}", n=n, ms=ms)

    pyr = {f"p{lvl}": jnp.asarray(rng.standard_normal(
        (256 >> (lvl - 2), 256 >> (lvl - 2), 256)).astype(np.float32))
        for lvl in (2, 3, 4, 5)}
    for r in (256, 1000):
        ctr = rng.uniform(20, 1000, (r, 2))
        size = np.exp(rng.uniform(np.log(8), np.log(500), (r, 2)))
        rois = jnp.asarray(np.clip(np.concatenate(
            [ctr - size / 2, ctr + size / 2], -1), 0, 1023
        ).astype(np.float32))
        for impl in ("onepass", "masked"):
            fn = jax.jit(functools.partial(
                roi_ops.multiscale_roi_align, impl=impl))
            ms = bench(fn, (pyr, rois), n=10) * 1e3
            print(f"roi_{impl:9s} r={r:6d} {ms:9.3f} ms", flush=True)
            if results_path:
                append_op_result(results_path, f"roi_align_{impl}",
                                 n=r, ms=ms)

    # Faster R-CNN second stage A/B (ROADMAP PR 3 follow-up): the SAME
    # jitted two-stage predict path, swapping only the model's
    # roi_align_impl knob — the row pair attributes the second-stage
    # cost to the one-pass packed gather vs the masked reference
    from deeplearning_tpu.core.registry import MODELS
    from deeplearning_tpu.models.detection.predict import build_predict_fn
    rcnn_img, rcnn_batch = 256, 2
    rcnn_images = jnp.asarray(rng.normal(
        size=(rcnn_batch, rcnn_img, rcnn_img, 3)).astype(np.float32))
    for impl in ("onepass", "masked"):
        model = MODELS.build("fasterrcnn_resnet18_fpn", num_classes=4,
                             roi_align_impl=impl)
        variables = model.init(jax.random.key(0),
                               jnp.zeros((1, rcnn_img, rcnn_img, 3)),
                               train=False)
        predict = build_predict_fn(model, "fasterrcnn_resnet18_fpn", 3,
                                   score_thresh=0.05, max_det=100)
        fn = jax.jit(functools.partial(
            predict, variables["params"],
            variables.get("batch_stats", {})))
        dt = bench(fn, (rcnn_images,), n=10)
        print(f"fasterrcnn_roi_{impl:8s} batch={rcnn_batch} "
              f"{dt * 1e3:9.2f} ms", flush=True)
        if results_path:
            append_result(results_path, f"fasterrcnn_roi_{impl}",
                          batch=rcnn_batch, step_ms=dt * 1e3,
                          img_per_s=rcnn_batch / dt, mfu_pct=0.0,
                          model="fasterrcnn_resnet18_fpn",
                          image_size=rcnn_img, roi_align_impl=impl)

    # end-to-end eval path: the per-step unit of evaluation/coco_eval —
    # one jitted forward + postprocess over a padded batch
    from deeplearning_tpu.models.detection.retinanet import (
        retinanet_anchors, retinanet_postprocess)
    img, batch = 512, 8
    model = MODELS.build("retinanet_resnet18_fpn", num_classes=80)
    variables = model.init(jax.random.key(0),
                           jnp.zeros((1, img, img, 3)), train=False)
    anchors = jnp.asarray(retinanet_anchors((img, img)))

    @jax.jit
    def eval_step(images):
        out = model.apply(variables, images, train=False)
        return retinanet_postprocess(out, anchors, (img, img),
                                     max_det=100, nms_impl="auto")

    images = jnp.asarray(rng.normal(
        size=(batch, img, img, 3)).astype(np.float32))
    dt = bench(eval_step, (images,), n=10)
    print(f"retinanet_eval_step batch={batch} {dt * 1e3:9.2f} ms "
          f"img/s={batch / dt:8.1f}", flush=True)
    if results_path:
        append_result(results_path, "retinanet_eval_e2e", batch=batch,
                      step_ms=dt * 1e3, img_per_s=batch / dt, mfu_pct=0.0,
                      model="retinanet_resnet18_fpn", image_size=img)


def time_serve_set(results_path=None):
    """Serving-path sweep (serve/ + tools/loadgen.py): the sequential
    per-request baseline vs dynamic micro-batching at several
    concurrencies, on a dispatch-dominated model so the rows isolate the
    batching win rather than raw conv throughput. On TPU, adds a
    ViT-B/16 closed-loop row — the bucket-calibration input for the
    ROADMAP follow-up."""
    from loadgen import (append_serve_row, make_images, run_closed_loop,
                         run_sequential)

    from deeplearning_tpu.serve import InferenceEngine, MicroBatcher

    engine = InferenceEngine("mnist_fcn", num_classes=10, image_size=28,
                             batch_buckets=(1, 8, 64))
    images = make_images(64, 28)
    rec = run_sequential(engine, images, 256)
    print(f"serve_sequential          {rec['req_per_s']:8.1f} req/s "
          f"p99={rec['p99_ms']:7.2f} ms", flush=True)
    if results_path:
        append_serve_row(results_path, rec, model="mnist_fcn")
    base = rec["req_per_s"]
    for conc in (8, 64):
        with MicroBatcher(engine, max_wait_ms=5.0) as mb:
            rec = run_closed_loop(mb, images, conc, 256)
        print(f"serve_closed  conc={conc:4d} {rec['req_per_s']:8.1f} "
              f"req/s p99={rec['p99_ms']:7.2f} ms "
              f"occ={rec['batch_occupancy']:.2f} "
              f"x{rec['req_per_s'] / max(base, 1e-9):.2f}", flush=True)
        if results_path:
            append_serve_row(results_path, rec, model="mnist_fcn",
                             speedup=round(rec["req_per_s"]
                                           / max(base, 1e-9), 2))

    if jax.default_backend() == "tpu":
        # on-chip row: the model the repo actually trains, served at its
        # natural buckets — feeds the v4 bucket-calibration follow-up
        engine = InferenceEngine("vit_base_patch16_224", num_classes=1000,
                                 image_size=224,
                                 batch_buckets=(1, 8, 32))
        images = make_images(32, 224)
        with MicroBatcher(engine, max_wait_ms=5.0) as mb:
            rec = run_closed_loop(mb, images, 32, 128)
        print(f"serve_closed_vit conc=32 {rec['req_per_s']:8.1f} req/s "
              f"p99={rec['p99_ms']:7.2f} ms", flush=True)
        if results_path:
            append_serve_row(results_path, rec,
                             model="vit_base_patch16_224")


def time_zoo_set(results_path=None):
    """Multi-tenant residency sweep (serve/zoo.py): per-model e2e p99
    for a model served SOLO vs as one of THREE residents taking mixed
    traffic, at fp32 vs int8 weight residency. Each variant row carries
    the zoo's resident weight bytes, the backend's ``hbm_snapshot``
    bytes-in-use (0 on CPU — no memory_stats), and the eviction count,
    so the density claim (int8 ≈ 4× more models per chip) and the
    isolation claim (a co-resident's p99 stays near solo) are both read
    off mfu_results.jsonl."""
    from loadgen import append_serve_row, make_images, run_closed_loop

    from deeplearning_tpu.obs.xla import hbm_snapshot
    from deeplearning_tpu.serve import MicroBatcher, ModelZoo

    def hbm_in_use():
        snap = hbm_snapshot()
        return sum(int(d.get("bytes_in_use") or 0)
                   for d in snap.get("devices") or [])

    tenants = {"fcn_a": "mnist_fcn", "fcn_b": "mnist_fcn",
               "cnn": "mnist_cnn"}
    buckets = (1, 8, 32)
    n_req, conc = 192, 16
    images = {a: make_images(buckets[-1], 28) for a in tenants}

    for quant in ("fp32", "int8"):
        for label, aliases in (("solo", ["fcn_a"]),
                               ("resident3", sorted(tenants))):
            zoo = ModelZoo()
            for alias in aliases:
                zoo.register(alias, tenants[alias], weight_quant=quant,
                             num_classes=10, image_size=28,
                             batch_buckets=buckets)
                zoo.load(alias, wait=True)
            mix = {a: 1.0 / len(aliases) for a in aliases}
            with MicroBatcher(zoo=zoo, max_wait_ms=2.0) as mb:
                rec = run_closed_loop(mb, images[aliases[0]], conc,
                                      n_req, mix=mix,
                                      images_by_model=images)
            zs = zoo.stats()
            resident_bytes = sum(m["bytes"]
                                 for m in zs["models"].values())
            row_name = f"zoo_{label}_{quant}"
            print(f"{row_name:22s} req/s={rec['req_per_s']:8.1f} "
                  f"weights={resident_bytes:9d}B "
                  f"hbm={hbm_in_use():11d}B "
                  f"evictions={zs['evictions']}", flush=True)
            for alias, sub in sorted(rec["models"].items()):
                print(f"  {alias:8s} p99={sub['p99_ms']:8.2f} ms "
                      f"completed={sub['completed']}", flush=True)
                if results_path:
                    append_serve_row(
                        results_path, sub, model=alias, variant=row_name,
                        weight_quant=quant, residency=len(aliases),
                        resident_bytes=resident_bytes,
                        hbm_bytes_in_use=hbm_in_use(),
                        evictions=zs["evictions"])


def time_obs_set(results_path=None):
    """Observability-overhead A/B (obs/spans.py): the same jitted train
    step timed with span tracing disabled vs enabled (per-step
    ``step_span`` bracketing, min-of-reps). The rows quantify the README
    "Observability policy" <2% budget on the real step; on CPU a small
    model keeps the run inside the tier-1 window, on TPU the ViT-B/16
    step gives the production number."""
    from bench_util import append_op_result, obs_overhead

    from deeplearning_tpu.core.registry import MODELS
    from deeplearning_tpu.train import TrainState, make_train_step
    from deeplearning_tpu.train.classification import make_loss_fn
    from deeplearning_tpu.train.optim import build_optimizer
    from deeplearning_tpu.train.schedules import build_schedule

    on_tpu = jax.default_backend() == "tpu"
    model_name, size, chans, batch = (
        ("vit_base_patch16_224", 224, 3, 128) if on_tpu
        else ("mnist_fcn", 28, 1, 64))
    model = MODELS.build(model_name, num_classes=1000 if on_tpu else 10)
    rng = jax.random.key(0)
    params = model.init(rng, jnp.zeros((1, size, size, chans)),
                        train=False)["params"]
    tx = build_optimizer("sgd", build_schedule("constant", base_lr=1e-2),
                         params=params)
    state = TrainState.create(apply_fn=model.apply, params=params, tx=tx)
    gen = np.random.default_rng(0)
    data = {"image": jnp.asarray(gen.normal(
                size=(batch, size, size, chans)), jnp.float32),
            "label": jnp.asarray(gen.integers(
                0, 1000 if on_tpu else 10, batch), jnp.int32)}
    step = jax.jit(make_train_step(make_loss_fn()))

    def one_step(s, b, r):
        _, m = step(s, b, r)
        return m["loss"]

    n = 20 if on_tpu else 50
    res = obs_overhead(one_step, (state, data, rng), n=n, reps=3)
    print(f"obs_spans_off {model_name} {res['spans_off_ms']:9.3f} ms/step",
          flush=True)
    print(f"obs_spans_on  {model_name} {res['spans_on_ms']:9.3f} ms/step "
          f"overhead={res['overhead_pct']:+.3f}% "
          f"within_2pct={res['within_budget']}", flush=True)
    if results_path:
        append_op_result(results_path, "obs_spans_off", n=n,
                         ms=res["spans_off_ms"], model=model_name)
        append_op_result(results_path, "obs_spans_on", n=n,
                         ms=res["spans_on_ms"], model=model_name,
                         overhead_pct=res["overhead_pct"],
                         within_2pct=res["within_budget"])
    return res


def time_shard_set(results_path=None):
    """Weight-update sharding A/B (ISSUE 10 tentpole): the same train
    step timed replicated vs zero1 vs zero1+int8 on the full device
    mesh. Each row carries step time, per-device optimizer-state bytes
    (the HBM win ZeRO-1 buys — ~1/dp of replicated), compiled-HLO
    collective bytes, and the compiler's ``memory_analysis`` argument
    bytes when available. On TPU this runs ViT-B/16; on CPU the mnist
    model keeps the sweep inside the tier-1 window."""
    from bench_util import append_op_result

    from deeplearning_tpu.analysis.jaxpr import hlo_collective_bytes
    from deeplearning_tpu.core.registry import MODELS
    from deeplearning_tpu.parallel.mesh import MeshConfig, build_mesh
    from deeplearning_tpu.parallel.sharding import tree_bytes_per_device
    from deeplearning_tpu.train import TrainState, make_train_step
    from deeplearning_tpu.train.classification import make_loss_fn
    from deeplearning_tpu.train.optim import build_optimizer
    from deeplearning_tpu.train.schedules import build_schedule
    from deeplearning_tpu.train.steps import shard_state

    on_tpu = jax.default_backend() == "tpu"
    model_name, size, chans, per_dev = (
        ("vit_base_patch16_224", 224, 3, 16) if on_tpu
        else ("mnist_fcn", 28, 1, 8))
    mesh = build_mesh(MeshConfig(data=-1))
    n_dev = mesh.shape["data"] * mesh.shape["fsdp"]
    batch = per_dev * n_dev
    model = MODELS.build(model_name, num_classes=1000 if on_tpu else 10)
    rng = jax.random.key(0)
    init_params = model.init(rng, jnp.zeros((1, size, size, chans)),
                             train=False)["params"]
    gen = np.random.default_rng(0)
    data = {"image": jnp.asarray(gen.normal(
                size=(batch, size, size, chans)), jnp.float32),
            "label": jnp.asarray(gen.integers(
                0, 1000 if on_tpu else 10, batch), jnp.int32)}

    variants = (("replicated", "replicated", "fp32"),
                ("zero1", "zero1", "fp32"),
                ("zero1_int8", "zero1", "int8"))
    out = {}
    for name, wu, comm in variants:
        tx = build_optimizer("adamw",
                             build_schedule("constant", base_lr=1e-3),
                             params=init_params)
        state = TrainState.create(apply_fn=model.apply,
                                  params=init_params, tx=tx)
        state = shard_state(state, mesh, zero1=(wu == "zero1"))
        opt_bytes = tree_bytes_per_device(state.opt_state)
        step = make_train_step(make_loss_fn(), mesh=mesh, donate=False,
                               weight_update=wu, grad_comm=comm)
        compiled = step.lower(state, data, rng).compile()
        coll = sum(hlo_collective_bytes(compiled).values())
        arg_bytes = None
        try:
            ma = compiled.memory_analysis()
            arg_bytes = int(getattr(ma, "argument_size_in_bytes", 0))
        # dltpu: allow(DLT104) memory_analysis is a backend-optional surface
        except Exception:  # noqa: BLE001
            pass
        state, metrics = compiled(state, data, rng)   # warmup
        float(metrics["loss"])
        n = 20 if on_tpu else 30
        t0 = time.perf_counter()
        for _ in range(n):
            state, metrics = compiled(state, data, rng)
        float(metrics["loss"])
        ms = (time.perf_counter() - t0) / n * 1e3
        print(f"shard_{name:<11s} {model_name} {ms:9.3f} ms/step "
              f"opt_bytes/dev={opt_bytes} collective_bytes={coll}",
              flush=True)
        if results_path:
            append_op_result(results_path, f"shard_{name}", n=batch,
                             ms=ms, model=model_name, devices=n_dev,
                             opt_state_bytes_per_device=opt_bytes,
                             collective_bytes=coll,
                             argument_bytes=arg_bytes)
        out[name] = {"ms": ms, "opt_bytes": opt_bytes,
                     "collective_bytes": coll}
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--set", default="batch",
                    choices=["batch", "attn", "all", "r5", "decomp",
                             "feed", "detect", "serve", "obs", "shard",
                             "zoo"])
    args = ap.parse_args()

    results = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "mfu_results.jsonl")
    if args.set in ("batch", "all"):
        for batch in (128, 160, 192, 256):
            time_variant("naive_f32softmax", batch)
    if args.set in ("attn", "all"):
        from deeplearning_tpu.ops.attention import flash_attn_adapter
        time_variant("bf16_softmax", 128, attn_fn=bf16_softmax_attention)
        time_variant("bf16_softmax", 256, attn_fn=bf16_softmax_attention)
        time_variant("flash_pallas", 128, attn_fn=flash_attn_adapter)
        time_variant("flash_pallas", 256, attn_fn=flash_attn_adapter)
    if args.set == "r5":
        # round-5 single-chip MFU pushes on the ViT-B/16 step. The
        # DEFAULT model is now tanh-GELU + matmul patch embed, so the
        # naive row is the fast path and the context restores the conv
        # for the A/B (first measured 2026-07-31: conv 50.87% vs matmul
        # 52.03%; bf16 softmax REGRESSES to 48.52% — f32 upcast fuses
        # better than bf16 exp)
        time_variant("patch_matmul_b128", 128, results_path=results)
        time_variant("bf16_softmax_b128", 128,
                     attn_fn=bf16_softmax_attention, results_path=results)
        with patch_embed_as_conv():
            time_variant("patch_conv_b128", 128, results_path=results)
    if args.set == "detect":
        time_detect_set(results_path=results)
    if args.set == "serve":
        time_serve_set(results_path=results)
    if args.set == "zoo":
        time_zoo_set(results_path=results)
    if args.set == "obs":
        time_obs_set(results_path=results)
    if args.set == "shard":
        time_shard_set(results_path=results)
    if args.set == "feed":
        # feed-side A/B for the MFU claim: serial blocking H2D vs the
        # threaded prefetch pipeline, same step, real per-iter batches
        time_feed_variant("feed_serial_b128", 128, depth=0,
                          results_path=results)
        time_feed_variant("feed_prefetch_b128", 128, depth=2,
                          results_path=results)
        time_feed_variant("feed_prefetch_deep_b128", 128, depth=4,
                          results_path=results)
    if args.set == "decomp":
        # empirical step-time decomposition (ceiling analysis): replace a
        # subsystem with identity and read the step-time delta vs the
        # full model. FLOPs drop too, so compare step_ms, not mfu_pct.
        time_variant("decomp_full", 128, results_path=results)
        time_variant("decomp_attn_identity", 128,
                     attn_fn=lambda q, k, v, **_: v, results_path=results)

        def scores_only(q, k, v, **_):
            # QK^T + softmax + AV with no f32 upcast and no scaling:
            # isolates the materialized-scores HBM cost vs numerics cost
            attn = jnp.einsum("bqhd,bkhd->bhqk", q, k)
            attn = jax.nn.softmax(attn, axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", attn, v)

        time_variant("decomp_attn_bf16_noscale", 128, attn_fn=scores_only,
                     results_path=results)

        def padded_attn(q, k, v, **_):
            # pad N 197→256 inside attention only: aligned MXU tiles at
            # the cost of +69% attention FLOPs (a tiny absolute number)
            n = q.shape[1]
            pad = (-n) % 128
            padw = ((0, 0), (0, pad), (0, 0), (0, 0))
            qp, kp, vp = (jnp.pad(t, padw) for t in (q, k, v))
            scale = q.shape[-1] ** -0.5
            attn = jnp.einsum("bqhd,bkhd->bhqk", qp * scale, kp)
            mask = jnp.arange(kp.shape[1]) < n
            attn = jnp.where(mask[None, None, None, :], attn, -jnp.inf)
            attn = jax.nn.softmax(attn.astype(jnp.float32),
                                  axis=-1).astype(q.dtype)
            return jnp.einsum("bhqk,bkhd->bqhd", attn, vp)[:, :n]

        time_variant("decomp_attn_pad256", 128, attn_fn=padded_attn,
                     results_path=results)


if __name__ == "__main__":
    main()
