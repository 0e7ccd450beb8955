#!/usr/bin/env python
"""Chip smoke: the main path, once, on the TPU, through the entry points
a user calls. The quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one chip: train, serve, kernels
    python chip_smoke.py --chips 4    # four chips: data-parallel training
                                      # against one of those chips, only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--chips 4]
                                      # the same phase functions at a tiny
                                      # size on the CPU (with --chips 4:
                                      # XLA_FLAGS=--xla_force_host_platform_device_count=4)

One process, the only one that touches JAX (a chip belongs to one process
at a time). Without ``--rehearse`` it needs a TPU: where
``jax.devices()[0].platform`` is anything else it says so, prints no
result and exits 2 before any phase. Every phase prints one JSON line as
it finishes; a failed check raises, so the run cannot end with 0. The
last line of a chip run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
A rehearsal's last line has no ``ok`` key and names the CPU it ran on.

Phases (full size: ``configs/vit_b16_imagenet.yaml`` unchanged — ViT-B/16,
1,000 classes, 224², bf16, global batch 128, all 12 layers, random weights
and synthetic data from the seed):

- train   ``tools/train.py``'s own ``build_trainer`` (config_cli →
          build_mesh → DataLoader → DevicePrefetcher → AOT precompile)
          then ``Trainer.train``: 8 optimizer steps and one eval pass.
- serve   ``tools/serve.py``'s own parser, ``build_engine`` and
          ``build_batcher`` at its default buckets; 28 requests, first
          one at a time, then in bursts from 4 threads at once; every
          answer against a direct ``model.apply`` on the same image.
- kernels ``nms(impl="auto")`` under ``jax.vmap`` at YOLOX-s's 8,400
          candidates against ``nms_reference``; the fused Pallas
          ``window_attention`` at Swin-T stage 1 and ``global_attention``
          at ViT-B/16's 197 tokens (output and ``dqkv``), each against its
          lax path.
- dp      (``--chips 4`` only) the train phase's steps on a 4-way data
          mesh against the same steps on one chip, same seed, data and
          global batch; then a few steps at 128 images a chip.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import re
import statistics
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [ROOT, os.path.join(ROOT, "tools")]

CFG = os.path.join(ROOT, "configs", "vit_b16_imagenet.yaml")
TRAIN_STEPS = 8

FULL = {
    "train": ["--cfg", CFG],
    "serve": ["--model", "vit_base_patch16_224", "--num-classes", "1000",
              "--size", "224"],
    "nms": {"batch": 8, "n": 8400, "span": 640.0, "wh_max": 96.0},
    # Swin-T stage 1 at batch 32: 56x56 tokens in 7x7 windows
    "window": {"batch": 32, "res": 56, "heads": 3, "d": 32},
    # ViT-B/16 at batch 30: 197 tokens, a ragged last block of images
    "global": {"batch": 30, "tokens": 197, "heads": 12, "d": 64},
    "dp_user_batch": 512,
}
TINY = {
    "train": ["--cfg", CFG, "model.name=vit_micro_patch4_56",
              "model.num_classes=10", "data.image_size=56",
              "data.global_batch=16"],
    "serve": ["--model", "vit_micro_patch4_56", "--num-classes", "10",
              "--size", "56"],
    "nms": {"batch": 2, "n": 1100, "span": 96.0, "wh_max": 24.0},
    "window": {"batch": 4, "res": 14, "heads": 3, "d": 32},
    "global": {"batch": 3, "tokens": 50, "heads": 4, "d": 32},
    "dp_user_batch": 64,
}

# Stated tolerances. Everything compared is computed in bf16 (8 mantissa
# bits, 2**-8 = 3.9e-3 relative per rounding) by two differently fused
# programs of the same mathematics.
PROB_RTOL = 2e-2       # served softmax row vs direct model.apply
ATTN_TOL = 3e-2        # fused vs lax window / global attention, outputs O(1)
DP_LOSS_ATOL = 1e-2    # per-step loss, 4-way mesh vs one chip (loss ~6.9)
DP_GNORM_RTOL = 5e-2   # per-step global grad norm, same pair


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(**rec) -> None:
    print(json.dumps(rec), flush=True)


# ----------------------------------------------------------- bookkeeping
class CacheCounter:
    """JAX's own persistent-cache verdicts since the last ``take()``:
    every compile of the phase, the tiny ones included."""

    def __init__(self):
        from deeplearning_tpu.obs.xla import jax_cache_counts
        self._counts = jax_cache_counts
        self._last = self._counts()

    def take(self) -> dict:
        now = self._counts()
        out = {k: now[k] - self._last[k] for k in now}
        self._last = now
        return out


def cache_entries(cache_dir) -> int:
    return len(os.listdir(cache_dir)) if cache_dir and os.path.isdir(
        cache_dir) else 0


def peak_bytes() -> list:
    """``peak_bytes_in_use`` of every device (None where the backend
    reports no memory stats, as the CPU does)."""
    import jax
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def named_compiles(prefix: str) -> list:
    """The repo's own compile telemetry for the AOT compiles this phase
    made: name, seconds, and JAX's verdict on its persistent cache."""
    from deeplearning_tpu.obs.xla import compile_events
    return [{"fn": e["fn"], "seconds": e["seconds"],
             "cache_hit": e["cache_hit"]}
            for e in compile_events() if e["fn"].startswith(prefix)]


# ----------------------------------------------------------------- train
class StepRecorder:
    """Trainer callbacks: wait for each step and keep its time, loss and
    gradient norm; remember how the first batch was laid out and what
    the eval pass returned."""

    def __init__(self, trainer):
        self.losses, self.grad_norms, self.step_s = [], [], []
        self.first_batch = None
        self.evals = {}
        self._t0 = 0.0
        trainer.callbacks.register("before_iter", self._before)
        trainer.callbacks.register("after_iter", self._after)
        trainer.callbacks.register("on_evaluate", self._evaluated)

    def _before(self, trainer, batch):
        if self.first_batch is None:
            image = batch["image"]
            self.first_batch = {
                "shape": list(image.shape),
                "shard_shapes": sorted({tuple(s.data.shape) for s in
                                        image.addressable_shards}),
                "devices": sorted(s.device.id for s in
                                  image.addressable_shards)}
        self._t0 = time.perf_counter()

    def _after(self, trainer, metrics):
        import jax
        jax.block_until_ready(metrics)
        self.step_s.append(time.perf_counter() - self._t0)
        self.losses.append(float(metrics["loss"]))
        self.grad_norms.append(float(metrics["grad_norm"]))

    def _evaluated(self, trainer, results):
        self.evals = dict(results)


def run_trainer(argv, platform, steps=TRAIN_STEPS, devices=None):
    """config_cli → build_trainer → Trainer.train, with the checks every
    training run of this smoke must pass. Returns (trainer, facts for the
    phase's JSON line)."""
    import jax
    import numpy as np

    import train as train_cli
    from deeplearning_tpu.core.config import config_cli

    cfg = config_cli(train_cli.Config(), argv)
    cfg = dataclasses.replace(cfg, data=dataclasses.replace(
        cfg.data, n_train=steps * cfg.data.global_batch))
    t0 = time.perf_counter()
    trainer = train_cli.build_trainer(cfg, devices=devices)
    build_s = time.perf_counter() - t0
    rec = StepRecorder(trainer)
    t0 = time.perf_counter()
    state = trainer.train()
    train_s = time.perf_counter() - t0

    check(len(rec.losses) == steps, f"ran {len(rec.losses)} steps, "
          f"wanted {steps}")
    check(all(np.isfinite(rec.losses)), f"non-finite loss: {rec.losses}")
    check(rec.losses[-1] != rec.losses[0],
          f"loss never moved: {rec.losses}")
    guard = trainer.train_step
    check(guard.retraces == 0 and guard.n_signatures == 1,
          f"train step saw {guard.n_signatures} argument signatures")
    check(guard.fn._cache_size() == 1,
          f"train step holds {guard.fn._cache_size()} traced programs")
    leaves = jax.tree.leaves(state)
    where = {d.platform for x in leaves for d in x.devices()}
    check(where == {platform}, f"state lives on {where}, not {platform}")
    check(rec.evals and all(np.isfinite(v) for v in rec.evals.values()),
          f"eval pass gave {rec.evals}")
    facts = {
        "model": cfg.model.name, "global_batch": cfg.data.global_batch,
        "image_size": cfg.data.image_size, "steps": steps,
        "build_s": round(build_s, 2),
        "compile_s": round(trainer.precompile_seconds, 2),
        "train_s": round(train_s, 2),
        "first_step_s": round(rec.step_s[0], 4),
        "step_s": [round(s, 4) for s in rec.step_s[1:]],
        "step_s_median": statistics.median(rec.step_s[1:]),
        "losses": rec.losses, "grad_norms": rec.grad_norms,
        "eval": rec.evals, "batch": rec.first_batch,
        "mesh": {k: v for k, v in trainer.train_loader.loader.mesh.shape
                 .items() if v > 1},     # loader under the prefetcher
    }
    return trainer, facts


def phase_train(size, platform, cache):
    _, facts = run_trainer(size["train"], platform)
    emit(phase="train", **facts, compiles=named_compiles("train_step"),
         jax_cache=cache.take(), peak_bytes_in_use=peak_bytes())


# ----------------------------------------------------------------- serve
def phase_serve(size, platform, cache):
    import jax
    import numpy as np

    import serve as serve_cli
    from deeplearning_tpu import hub
    from deeplearning_tpu.obs import spans
    from deeplearning_tpu.obs import threads as obs_threads

    args = serve_cli.build_parser().parse_args(size["serve"])
    t0 = time.perf_counter()
    engine = serve_cli.build_engine(args)
    warm_s = time.perf_counter() - t0
    n_buckets = len(engine.buckets)
    check(engine.trace_count == engine.compile_count == n_buckets,
          f"after warmup: traces {engine.trace_count}, compiles "
          f"{engine.compile_count}, buckets {n_buckets}")
    # 4 requests one at a time, then two bursts from 4 threads at once:
    # 2 each (8 in flight) and 4 each (16 in flight)
    n_seq, n_threads, bursts = 4, 4, (2, 4)
    n_req = n_seq + n_threads * sum(bursts)
    images = np.random.default_rng(0).normal(
        size=(n_req, args.size, args.size, 3)).astype(np.float32)
    answers = [None] * n_req
    latency = [None] * n_req
    errors = []

    def ask(batcher, ids):
        try:
            sent = [(i, time.perf_counter(), batcher.submit(images[i]))
                    for i in ids]
            for i, t_sub, handle in sent:
                answers[i] = handle.result(timeout=args.timeout_s)
                latency[i] = time.perf_counter() - t_sub
        except Exception as exc:  # noqa: BLE001 - re-raised below
            errors.append(exc)

    tracer_was_on = spans.enabled()
    tracer = spans.enable()
    seen_before = len(tracer.events())
    with serve_cli.build_batcher(args, engine) as batcher:
        for i in range(n_seq):            # one in flight: smallest bucket
            ask(batcher, [i])
        gate = threading.Barrier(n_threads)

        def client(first, n):
            gate.wait(timeout=30)
            ask(batcher, range(first, first + n))
        first = n_seq
        for per_thread in bursts:
            threads = [obs_threads.spawn(
                client, args=(first + t * per_thread, per_thread),
                name=f"smoke-client-{t}") for t in range(n_threads)]
            first += n_threads * per_thread
            for th in threads:
                th.join(timeout=args.timeout_s + 30)
                check(not th.is_alive(), f"{th.name} never finished")
        snap = batcher.telemetry.snapshot()
    if errors:
        raise errors[0]
    dispatched = [e["args"]["bucket"] for e in tracer.events()[seen_before:]
                  if e.get("name") == "serve/dispatch"]
    if not tracer_was_on:
        spans.disable()
    check(len(set(dispatched)) >= 2,
          f"only buckets {set(dispatched)} dispatched")
    check(engine.trace_count == engine.compile_count == n_buckets,
          f"serving retraced: traces {engine.trace_count}, compiles "
          f"{engine.compile_count}, buckets {n_buckets}")

    # reference: the same registry model and seed, applied directly
    model, variables, _ = hub.load(
        args.model, num_classes=args.num_classes,
        input_shape=(1, args.size, args.size, 3), seed=0)
    want = np.asarray(jax.jit(lambda v, x: jax.nn.softmax(
        model.apply(v, x, train=False), -1))(variables, images))
    got = np.stack(answers)
    check(got.shape == want.shape and np.isfinite(got).all(),
          f"answers {got.shape} vs reference {want.shape}")
    worst = float(np.max(np.abs(got - want) / want))
    check(worst <= PROB_RTOL, f"served probabilities off by {worst:.3g} "
          f"relative (tolerance {PROB_RTOL})")
    # each answer is its own image's row, not a neighbour's or padding
    nearest = np.argmin(np.abs(got[:, None] - want[None]).sum(-1), axis=1)
    check((nearest == np.arange(n_req)).all(),
          f"answers demultiplexed to the wrong requests: {nearest}")

    emit(phase="serve", model=args.model, image_size=args.size,
         buckets=list(engine.buckets), warmup_s=round(warm_s, 2),
         warmup_s_per_bucket=engine.stats()["warmup_seconds"],
         requests=n_req, dispatched_buckets=dispatched,
         request_s_one_at_a_time=[round(s, 4) for s in latency[:n_seq]],
         request_s_burst=[round(s, 4) for s in latency[n_seq:]],
         request_s_median=statistics.median(latency),
         max_rel_err=worst, rel_tol=PROB_RTOL,
         trace_count=engine.trace_count, compile_count=engine.compile_count,
         completed=snap["completed"], rejected=snap["rejected"],
         timed_out=snap["timed_out"],
         compiles=named_compiles("serve/"), jax_cache=cache.take(),
         peak_bytes_in_use=peak_bytes())


# --------------------------------------------------------------- kernels
def compile_and_time(lowered, *args):
    """(result, compile seconds, seconds of the second run) of one
    lowered program; every run is waited for."""
    import jax
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, compile_s, time.perf_counter() - t0


def phase_kernels(size, platform, cache):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning_tpu.ops import nms as nms_ops
    from deeplearning_tpu.ops import window_utils as wu
    from deeplearning_tpu.ops.pallas.common import interpret_mode
    from deeplearning_tpu.ops.pallas.window_attention import window_attention

    on_tpu = platform == "tpu"
    check(interpret_mode() is (not on_tpu),
          f"interpret_mode() is {interpret_mode()} on {platform}")

    # --- NMS: impl="auto" under vmap, as every detector calls it
    c = size["nms"]
    rng = np.random.default_rng(0)
    ctr = rng.uniform(0, c["span"], (c["batch"], c["n"], 2))
    wh = rng.uniform(4.0, c["wh_max"], (c["batch"], c["n"], 2))
    boxes = jnp.asarray(np.concatenate([ctr - wh / 2, ctr + wh / 2], -1),
                        jnp.float32)
    scores = jnp.asarray(rng.uniform(0, 1, (c["batch"], c["n"])),
                         jnp.float32)
    kw = dict(iou_threshold=0.5, max_out=100)
    lowered = jax.jit(jax.vmap(functools.partial(
        nms_ops.nms, impl="auto", **kw))).lower(boxes, scores)
    # the route "auto" took: the Mosaic kernel is in the program, or not
    in_program = "tpu_custom_call" in lowered.as_text()
    check(in_program == on_tpu, f"nms(impl='auto', N={c['n']}) on "
          f"{platform}: Pallas kernel in the program is {in_program}")
    (idx, valid), nms_compile_s, nms_run_s = compile_and_time(
        lowered, boxes, scores)
    reference = jax.jit(functools.partial(nms_ops.nms_reference, **kw))
    impls = {"auto": (np.asarray(idx), np.asarray(valid))}
    if not on_tpu:      # rehearse the kernel's own code, interpreted
        pal = jax.jit(jax.vmap(functools.partial(nms_ops.nms, impl="pallas",
                                                 **kw)))
        impls["pallas"] = tuple(map(np.asarray, pal(boxes, scores)))
    kept = 0
    for b in range(c["batch"]):     # one image at a time: N x N IoU each
        ref_idx, ref_valid = map(np.asarray, reference(boxes[b], scores[b]))
        kept += int(ref_valid.sum())
        for name, (got_idx, got_valid) in impls.items():
            check(np.array_equal(ref_valid, got_valid[b]),
                  f"nms {name}: valid mask differs on image {b}")
            bad = np.flatnonzero((ref_idx != got_idx[b]) & ref_valid)
            check(bad.size == 0, f"nms {name}: image {b} slot "
                  f"{bad[:1]} keeps {got_idx[b][bad[:1]]}, reference "
                  f"{ref_idx[bad[:1]]}")

    # --- window attention: fused kernel vs the lax path swin.py takes
    w = size["window"]
    mask = jnp.asarray(wu.shift_window_mask(w["res"], w["res"], 7, 3))
    bw = w["batch"] * mask.shape[0]
    k1, k2 = jax.random.split(jax.random.key(0))
    qkv = jax.random.normal(k1, (bw, 49, 3 * w["heads"] * w["d"]),
                            jnp.bfloat16)
    bias = 0.1 * jax.random.normal(k2, (w["heads"], 49, 49), jnp.float32)
    fused = jax.jit(functools.partial(window_attention, heads=w["heads"]))
    lax_path = jax.jit(lambda qkv, bias, m: wu.windowed_attention_reference(
        qkv.reshape(bw, 49, 3, w["heads"], w["d"]), bias, m))
    attn = {}
    for label, m in (("masked", mask), ("unmasked", None)):
        lowered = fused.lower(qkv, bias, m)
        in_program = "tpu_custom_call" in lowered.as_text()
        check(in_program == on_tpu, f"window attention {label} on "
              f"{platform}: Pallas kernel in the program is {in_program}")
        out, compile_s, run_s = compile_and_time(lowered, qkv, bias, m)
        ref = lax_path(qkv, bias, m)
        out, ref = (np.asarray(x, np.float32) for x in (out, ref))
        check(np.isfinite(out).all(), f"window attention {label}: "
              "non-finite output")
        err = float(np.max(np.abs(out - ref) / (1.0 + np.abs(ref))))
        check(err <= ATTN_TOL, f"window attention {label}: off by "
              f"{err:.3g} (tolerance {ATTN_TOL})")
        attn[label] = {"compile_s": round(compile_s, 2), "run_s": run_s,
                       "max_err": err}

    # --- global attention: fused kernels vs the lax path vit.py keeps
    from deeplearning_tpu.models.classification.vit import \
        dot_product_attention
    from deeplearning_tpu.ops.pallas.global_attention import global_attention
    gl = size["global"]
    heads, d = gl["heads"], gl["d"]
    rows = (gl["batch"], gl["tokens"])
    k1, k2 = jax.random.split(jax.random.key(1))
    qkv = jax.random.normal(k1, rows + (3 * heads * d,), jnp.bfloat16)
    weight = jax.random.normal(k2, rows + (heads * d,), jnp.bfloat16)

    def lax_attention(qkv):
        x = qkv.reshape(rows + (3, heads, d))
        return dot_product_attention(x[:, :, 0], x[:, :, 1],
                                     x[:, :, 2]).reshape(weight.shape)

    def with_grad(attend):
        def run(a, w):
            out, vjp = jax.vjp(attend, a)
            return out, vjp(w)[0]
        return jax.jit(run)

    lowered = with_grad(functools.partial(
        global_attention, heads=heads)).lower(qkv, weight)
    in_program = lowered.as_text().count("tpu_custom_call") >= 2
    check(in_program == on_tpu, f"global attention on {platform}: both "
          f"Pallas kernels in the program is {in_program}")
    got, glob_compile_s, glob_run_s = compile_and_time(lowered, qkv, weight)
    glob = {"compile_s": round(glob_compile_s, 2), "run_s": glob_run_s}
    for label, out, ref in zip(("out", "dqkv"), got,
                               with_grad(lax_attention)(qkv, weight)):
        out, ref = (np.asarray(x, np.float32) for x in (out, ref))
        check(np.isfinite(out).all(), f"global attention {label}: "
              "non-finite")
        err = float(np.max(np.abs(out - ref) / (1.0 + np.abs(ref))))
        check(err <= ATTN_TOL, f"global attention {label}: off by "
              f"{err:.3g} (tolerance {ATTN_TOL})")
        glob[f"{label}_max_err"] = err

    emit(phase="kernels", interpret_mode=interpret_mode(),
         nms={"impl": "auto", "shape": [c["batch"], c["n"]],
              "pallas_in_program": on_tpu, "kept": kept,
              "compile_s": round(nms_compile_s, 2), "run_s": nms_run_s,
              "matches_reference": True,
              "also_checked": sorted(set(impls) - {"auto"})},
         window_attention={"shape": [bw, 49, 3 * w["heads"] * w["d"]],
                           "tol": ATTN_TOL, **attn},
         global_attention={"shape": list(qkv.shape), "tol": ATTN_TOL,
                           **glob},
         jax_cache=cache.take(), peak_bytes_in_use=peak_bytes())


# ------------------------------------------------------------ four chips
def phase_dp(size, platform, cache):
    """Data-parallel GSPMD training over every chip against the same
    steps on one chip; then the batch a user would run."""
    import jax
    import numpy as np

    n_dev = len(jax.devices())
    steps = 6

    def dp_checks(trainer, facts):
        check(facts["mesh"] == {"data": n_dev}, f"mesh {facts['mesh']}")
        batch = facts["batch"]
        check(batch["devices"] == sorted(d.id for d in jax.devices()),
              f"batch shards on devices {batch['devices']}")
        per = batch["shard_shapes"]
        check(len(per) == 1
              and per[0][0] == facts["global_batch"] // n_dev,
              f"batch shards {per} of global batch "
              f"{facts['global_batch']}")
        leaf = jax.tree.leaves(trainer.state.params)[0]
        check(leaf.sharding.is_fully_replicated
              and len(leaf.sharding.device_set) == n_dev,
              f"parameters are laid out as {leaf.sharding}")
        n_allreduce = len(re.findall(r" all-reduce(?:-start)?\(",
                                     trainer._aot_step.as_text()))
        check(n_allreduce > 0, "compiled step holds no all-reduce")
        return n_allreduce

    trainer, many = run_trainer(size["train"], platform, steps)
    n_allreduce = dp_checks(trainer, many)
    peak_many = peak_bytes()
    del trainer                 # frees its state before the next build
    _, one = run_trainer(size["train"], platform, steps,
                         devices=jax.devices()[:1])
    check(one["mesh"] == {} and one["batch"]["devices"]
          == [jax.devices()[0].id], f"comparison ran on {one['batch']}")
    loss_gap = float(np.max(np.abs(np.subtract(many["losses"],
                                               one["losses"]))))
    gnorm_gap = float(np.max(np.abs(
        np.divide(many["grad_norms"], one["grad_norms"]) - 1.0)))
    check(loss_gap <= DP_LOSS_ATOL, f"per-step losses differ by "
          f"{loss_gap:.3g}: {many['losses']} vs {one['losses']}")
    check(gnorm_gap <= DP_GNORM_RTOL, f"per-step grad norms differ by "
          f"{gnorm_gap:.3g} relative: {many['grad_norms']} vs "
          f"{one['grad_norms']}")
    emit(phase="dp_vs_one_chip", devices=n_dev, steps=steps,
         all_reduce_in_step=n_allreduce, batch=many["batch"],
         params_replicated=True,
         losses_dp=many["losses"], losses_one=one["losses"],
         grad_norms_dp=many["grad_norms"],
         grad_norms_one=one["grad_norms"],
         max_loss_gap=loss_gap, loss_atol=DP_LOSS_ATOL,
         max_grad_norm_rel_gap=gnorm_gap, grad_norm_rtol=DP_GNORM_RTOL,
         compile_s_dp=many["compile_s"], compile_s_one=one["compile_s"],
         step_s_median_dp=many["step_s_median"],
         step_s_median_one=one["step_s_median"],
         peak_bytes_in_use_after_dp=peak_many,
         jax_cache=cache.take(), peak_bytes_in_use=peak_bytes())

    trainer, user = run_trainer(
        size["train"] + [f"data.global_batch={size['dp_user_batch']}"],
        platform, 4)
    dp_checks(trainer, user)
    emit(phase="dp_user_batch", note="one builder run", devices=n_dev,
         **user, compiles=named_compiles("train_step")[-1:],
         jax_cache=cache.take(), peak_bytes_in_use=peak_bytes())


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the data-parallel phase and its "
                         "one-chip comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU backend")
    args = ap.parse_args(argv)

    import jax
    first = jax.devices()[0]
    device = {"platform": first.platform, "kind": first.device_kind,
              "count": len(jax.devices())}
    want = "cpu" if args.rehearse else "tpu"
    if first.platform != want or device["count"] < args.chips:
        print(f"chip_smoke: needs {args.chips} {want} device(s), JAX "
              f"found {device}; no phase was run", file=sys.stderr)
        return 2

    from deeplearning_tpu.core.compile_cache import enable_compile_cache
    from deeplearning_tpu.data import native_decode
    cache_dir = enable_compile_cache()
    cache = CacheCounter()
    entries_before = cache_entries(cache_dir)
    t0 = time.perf_counter()
    native = native_decode.available()    # g++ build on first use
    emit(phase="start", device=device, rehearsal=args.rehearse,
         jax=jax.__version__, compile_cache_dir=cache_dir,
         compile_cache_entries=entries_before,
         native_imagedec=native, native_build_s=round(
             time.perf_counter() - t0, 2),
         decode_path="native libjpeg" if native else "Python (PIL): the "
         "native decoder could not be built or loaded here")

    size = TINY if args.rehearse else FULL
    phases = ([phase_dp] if args.chips == 4
              else [phase_train, phase_serve, phase_kernels])
    for phase in phases:
        phase(size, first.platform, cache)

    emit(phase="end", compile_cache_dir=cache_dir,
         compile_cache_entries_before=entries_before,
         compile_cache_entries_after=cache_entries(cache_dir),
         peak_bytes_in_use=peak_bytes())
    if args.rehearse:
        emit(rehearsal_ok=True, device=device)
    else:
        emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
