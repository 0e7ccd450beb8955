"""Shared timing helpers for the TPU microbenchmarks."""

import json
import os
import sys
import time

import jax

# Persistent XLA compile cache shared by every perf tool (wiring lives
# in deeplearning_tpu.core.compile_cache): importing this module turns
# it on, so each compile is paid once per machine, not once per tool.
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from deeplearning_tpu.core.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()


def append_result(path, variant, *, batch, step_ms, img_per_s, mfu_pct,
                  **extra):
    """Append one measurement to mfu_results.jsonl (single shared schema
    for perf_sweep.py and mfu_push.py rows).

    Stamps the fields every consumer needs to interpret a row — device,
    UTC time, and the GELU numerics mode (rows before/after the round-5
    tanh-default switch differ by ~3.8 MFU points on ViT). Returns the
    record so callers can print exactly what was written."""
    from deeplearning_tpu.core import numerics
    rec = {
        "variant": variant,
        "batch": batch,
        "step_ms": round(step_ms, 2),
        "img_per_s": round(img_per_s, 1),
        "mfu_pct": round(mfu_pct, 2),
        "gelu": "erf" if numerics.exact_enabled() else "tanh",
        "device": jax.devices()[0].device_kind,
        "utc": time.strftime("%Y-%m-%d %H:%M:%S", time.gmtime()),
    }
    rec.update(extra)
    with open(path, "a") as f:
        f.write(json.dumps(rec) + "\n")
    return rec


def append_op_result(path, op, *, n, ms, **extra):
    """Append one OP-level microbench row (the ``--set detect`` sweep and
    loadgen's serve rows) to the same jsonl as the step-level
    rows. Op rows carry {op, n, ms} instead of batch/step_ms/img_per_s so
    consumers can split the two schemas with ``"op" in rec``."""
    rec = {
        "op": op,
        "n": int(n),
        "ms": round(float(ms), 3),
        "device": jax.devices()[0].device_kind,
        "utc": time.strftime("%Y-%m-%d %H:%M:%S", time.gmtime()),
    }
    rec.update(extra)
    with open(path, "a") as f:
        f.write(json.dumps(rec) + "\n")
    return rec


def feed_stats(source):
    """Device-feed telemetry columns for bench rows.

    Accepts a ``DevicePrefetcher`` (calls its ``stats()``) or an
    already-built stats dict (e.g. ``Trainer.throughput_stats``) and
    returns the input-feed subset every perf row should carry —
    ``h2d_wait_frac`` + ``prefetch_occupancy`` are what let the next
    on-chip run attribute an MFU delta to feed overlap vs step compute."""
    stats = source.stats() if callable(getattr(source, "stats", None)) \
        else dict(source)
    keys = ("h2d_wait_frac", "prefetch_occupancy", "prefetch_depth",
            "data_wait_frac")
    return {k: round(float(stats[k]), 4) for k in keys if k in stats}


def bench(fn, args, n=30, warmup=3):
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def obs_overhead(step_fn, args, n=30, reps=3, budget_pct=2.0):
    """A/B the span-instrumented hot loop: the same ``step_fn(*args)``
    loop timed with tracing disabled vs enabled (each step bracketed in
    a ``step_span``, the Trainer's per-step instrumentation). Min-of-reps
    per arm absorbs host jitter — this measures the instrumentation
    floor, not scheduler noise. Returns the README "Observability
    policy" contract numbers: ``within_budget`` is the <=``budget_pct``%
    overhead assertion the bench smoke rides on."""
    from deeplearning_tpu.obs import spans

    def loop(instrument):
        out = None
        t0 = time.perf_counter()
        for i in range(n):
            if instrument:
                with spans.step_span("dispatch", i):
                    out = step_fn(*args)
            else:
                out = step_fn(*args)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    # warmup: compile + touch both code paths once
    jax.block_until_ready(step_fn(*args))
    was_enabled = spans.enabled()
    off = ms_on = float("inf")
    try:
        for _ in range(reps):
            spans.disable()
            off = min(off, loop(False))
            spans.enable()
            ms_on = min(ms_on, loop(True))
    finally:
        spans.enable() if was_enabled else spans.disable()
    overhead_pct = (ms_on - off) / off * 100.0 if off > 0 else 0.0
    return {
        "spans_off_ms": round(off / n * 1e3, 4),
        "spans_on_ms": round(ms_on / n * 1e3, 4),
        "overhead_pct": round(overhead_pct, 3),
        "within_budget": overhead_pct <= budget_pct,
        "budget_pct": budget_pct,
    }


def metrics_overhead(step_fn, args, n=30, reps=3, budget_pct=2.0):
    """A/B the metrics-instrumented hot loop: the same ``step_fn(*args)``
    loop with the registry disabled vs enabled, each step paying the
    per-step push a real instrumented loop pays (one counter ``inc`` +
    one histogram ``observe``). Min-of-reps per arm, same <=2% contract
    shape as ``obs_overhead`` — the fleet scrape surface must cost no
    more than the span tracer it sits next to."""
    from deeplearning_tpu.obs import metrics

    def loop():
        out = None
        t0 = time.perf_counter()
        for i in range(n):
            metrics.inc("dltpu_bench_steps_total")
            metrics.observe("dltpu_bench_step_ms", float(i))
            out = step_fn(*args)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    jax.block_until_ready(step_fn(*args))           # warmup: compile once
    was_enabled = metrics.enabled()
    off = on = float("inf")
    try:
        for _ in range(reps):
            metrics.disable()
            off = min(off, loop())
            metrics.enable()
            on = min(on, loop())
    finally:
        metrics.enable() if was_enabled else metrics.disable()
    overhead_pct = (on - off) / off * 100.0 if off > 0 else 0.0
    return {
        "metrics_off_ms": round(off / n * 1e3, 4),
        "metrics_on_ms": round(on / n * 1e3, 4),
        "overhead_pct": round(overhead_pct, 3),
        "within_budget": overhead_pct <= budget_pct,
        "budget_pct": budget_pct,
    }
