#!/usr/bin/env python
"""Load generator for the serving engine (closed- and open-loop).

  # 64 closed-loop clients against a dynamically-batched model
  python tools/loadgen.py --model mnist_fcn --num-classes 10 --size 28 \\
      --buckets 1,8,64 --mode compare --concurrency 64 --n 512

Modes:
- ``closed``: N concurrent clients, each submitting back-to-back
  (throughput under saturation — the MLPerf-server closed loop).
- ``open``: fixed-rate arrivals regardless of completions (latency under
  a target QPS; finds the knee where admission control kicks in).
- ``sequential``: one-at-a-time ``engine.infer`` — the predict.py-style
  baseline dynamic batching is measured against.
- ``compare``: sequential then closed, printing the speedup (the serve
  acceptance gate: batched ≥3× sequential at 64 clients on CPU).

Mixed multi-tenant traffic (one ``ModelZoo``, weighted per-request
model choice, per-model op rows):

  python tools/loadgen.py --mode closed --concurrency 32 --n 256 \\
      --mix "mnist_fcn=0.7,mnist_cnn=0.3" --size 28 --buckets 1,8,32

With ``--results FILE`` every run appends one ``serve_<mode>`` op row
({op, n, ms, device, utc, ...}) to that jsonl; ``--mix`` runs append one
row per tenant. Without the flag no file is written.

Fleet HTTP mode (``--mode open --fleet-urls`` / ``--fleet-dir``):
arrivals POST ``/predict`` to a replica fleet through a
``FleetRouter`` (round-robin, drains skipped, failover on 503,
deadline propagation, budgeted retries + tail hedging, per-replica
circuit breakers — README "Resilience policy"). Open-loop records
carry a per-second ``timeline`` (QPS split + p99 + retry/hedge/
deadline-miss counts) so recovery-after-fault can be asserted against
the trajectory, not the run-wide aggregate, and embed the router's
``resilience_stats()``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import numpy as np


def _percentiles_ms(lats):
    if not lats:
        return {"p50_ms": 0.0, "p90_ms": 0.0, "p99_ms": 0.0}
    p50, p90, p99 = (float(v) for v in
                     np.percentile(np.asarray(lats), [50, 90, 99]))
    return {"p50_ms": round(p50 * 1e3, 3), "p90_ms": round(p90 * 1e3, 3),
            "p99_ms": round(p99 * 1e3, 3)}


def make_images(n: int, size: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).normal(
        size=(n, size, size, 3)).astype(np.float32)


class Timeline:
    """Per-second QPS/latency buckets for the open-loop modes.

    The aggregate p99 of a 30 s run can look fine while 5 s of it were
    an outage; the recovery assertions ("p99 back in band within N
    seconds of the replacement warming") need the trajectory, not the
    summary. Submissions/rejections bucket at arrival time, completions
    and their latencies at completion time."""

    def __init__(self):
        self.t0 = time.perf_counter()
        self._lock = threading.Lock()
        self._buckets: dict = {}

    _KEYS = ("submitted", "completed", "rejected", "timed_out",
             "no_route", "retries", "hedged", "deadline_miss")

    def note(self, key: str, lat=None, n: int = 1) -> None:
        sec = int(time.perf_counter() - self.t0)
        with self._lock:
            row = self._buckets.setdefault(
                sec, {k: 0 for k in self._KEYS} | {"lats": []})
            row[key] += n
            if lat is not None:
                row["lats"].append(lat)

    def rows(self) -> list:
        with self._lock:
            out = []
            for sec in sorted(self._buckets):
                row = self._buckets[sec]
                out.append(
                    {"t": sec}
                    | {k: row[k] for k in self._KEYS}
                    | {"p99_ms": _percentiles_ms(row["lats"])["p99_ms"]})
            return out


def run_sequential(engine, images, n_requests: int) -> dict:
    """Unbatched baseline: requests served one at a time, each paying a
    full dispatch + materialize round-trip (tools/predict.py's shape)."""
    lats = []
    t0 = time.perf_counter()
    for i in range(n_requests):
        t1 = time.perf_counter()
        engine.infer(images[i % len(images)])
        lats.append(time.perf_counter() - t1)
    wall = time.perf_counter() - t0
    return {"mode": "sequential", "completed": n_requests, "rejected": 0,
            "timed_out": 0, "req_per_s": round(n_requests / wall, 1),
            "wall_s": round(wall, 3), **_percentiles_ms(lats)}


def parse_mix(raw: str) -> dict:
    """``--mix "a=0.7,b=0.3"`` → {alias: normalized weight}. A bare
    alias counts as weight 1 before normalization."""
    out = {}
    for part in raw.split(","):
        alias, _, w = part.partition("=")
        alias = alias.strip()
        if alias:
            out[alias] = float(w) if w else 1.0
    if not out:
        raise ValueError(f"empty --mix {raw!r}")
    total = sum(out.values())
    return {a: w / total for a, w in out.items()}


class _MixSampler:
    """Weighted per-request model choice + per-model tallies for the
    mixed-traffic loops. ``None`` mix degrades to the single-model path
    (model=None submits, one aggregate tally)."""

    def __init__(self, mix, images_by_model, images):
        self.mix = mix
        self.aliases = sorted(mix) if mix else [None]
        self.weights = (np.asarray([mix[a] for a in self.aliases])
                        if mix else None)
        self.images_by_model = images_by_model or {}
        self.images = images
        self.per = {a: {"completed": 0, "rejected": 0, "timed_out": 0,
                        "lats": []} for a in self.aliases}

    def pick(self, rng):
        if self.mix is None:
            return None, self.images
        alias = self.aliases[int(rng.choice(len(self.aliases),
                                            p=self.weights))]
        return alias, self.images_by_model.get(alias, self.images)

    def tally(self, alias, key, lat=None):
        row = self.per[alias]
        row[key] += 1
        if lat is not None:
            row["lats"].append(lat)

    def model_recs(self, mode: str, wall: float) -> dict:
        if self.mix is None:
            return {}
        out = {}
        for alias in self.aliases:
            row = self.per[alias]
            out[alias] = {
                "mode": mode, "model": alias,
                "mix_weight": round(self.mix[alias], 4),
                "completed": row["completed"],
                "rejected": row["rejected"],
                "timed_out": row["timed_out"],
                "req_per_s": round(row["completed"] / max(wall, 1e-9), 1),
                **_percentiles_ms(row["lats"])}
        return out


def run_closed_loop(batcher, images, concurrency: int, n_requests: int,
                    timeout_s: float = 30.0, mix=None,
                    images_by_model=None) -> dict:
    """``concurrency`` clients, each submit→materialize back-to-back
    until ``n_requests`` total complete. Backpressure rejections honor
    the retry-after hint (bounded, so a saturated queue slows clients
    down instead of losing work). With ``mix`` each request samples its
    target model by weight and the record carries per-model splits."""
    from concurrent.futures import TimeoutError as _FutTimeout

    from deeplearning_tpu.serve import DeadlineExceeded, Rejected

    lock = threading.Lock()
    state = {"launched": 0, "completed": 0, "rejected": 0, "timed_out": 0}
    lats = []
    sampler = _MixSampler(mix, images_by_model, images)

    def worker(wid: int):
        rng = np.random.default_rng(wid)
        while True:
            with lock:
                if state["launched"] >= n_requests:
                    return
                state["launched"] += 1
            alias, pool = sampler.pick(rng)
            img = pool[int(rng.integers(len(pool)))]
            t0 = time.perf_counter()
            try:
                handle = batcher.submit(img, model=alias)
                handle.result(timeout=timeout_s)
            except Rejected as r:
                with lock:
                    state["rejected"] += 1
                    if alias is not None:
                        sampler.tally(alias, "rejected")
                time.sleep(min(r.retry_after_s, 0.2))
                continue
            except (DeadlineExceeded, _FutTimeout):
                # a result that outlived timeout_s counts as timed out;
                # the worker keeps driving load instead of dying
                with lock:
                    state["timed_out"] += 1
                    if alias is not None:
                        sampler.tally(alias, "timed_out")
                continue
            lat = time.perf_counter() - t0
            with lock:
                state["completed"] += 1
                lats.append(lat)
                if alias is not None:
                    sampler.tally(alias, "completed", lat)

    from deeplearning_tpu.obs import threads as obs_threads
    threads = [obs_threads.spawn(worker, args=(w,), daemon=True,
                                 name=f"loadgen-closed-{w}", start=False)
               for w in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    snap = batcher.telemetry.snapshot()
    rec = {"mode": "closed", "concurrency": concurrency, **state,
           "req_per_s": round(state["completed"] / wall, 1),
           "wall_s": round(wall, 3), **_percentiles_ms(lats),
           "batch_occupancy": snap["batch_occupancy"],
           "queue_depth_mean": snap["queue_depth_mean"],
           "shed_batches": snap["shed_batches"]}
    models = sampler.model_recs("closed", wall)
    if models:
        rec["models"] = models
    return rec


def run_open_loop(batcher, images, rate_hz: float, duration_s: float,
                  timeout_s: float = 10.0, mix=None,
                  images_by_model=None) -> dict:
    """Fixed-rate arrivals: one submitter paces requests at ``rate_hz``;
    a resolver pool materializes results. Rejections are counted and
    DROPPED (open-loop semantics — the arrival process never waits).
    With ``mix`` each arrival samples its model by weight."""
    import queue as _queue

    from deeplearning_tpu.serve import DeadlineExceeded, Rejected

    handles: "_queue.Queue" = _queue.Queue()
    lock = threading.Lock()
    state = {"submitted": 0, "completed": 0, "rejected": 0,
             "timed_out": 0}
    lats = []
    sampler = _MixSampler(mix, images_by_model, images)
    timeline = Timeline()
    done = threading.Event()

    def resolver():
        while True:
            item = handles.get()
            if item is None:
                return
            t0, alias, handle = item
            try:
                handle.result(timeout=timeout_s)
            except (DeadlineExceeded, Exception):  # noqa: BLE001
                with lock:
                    state["timed_out"] += 1
                    if alias is not None:
                        sampler.tally(alias, "timed_out")
                timeline.note("timed_out")
                continue
            lat = time.perf_counter() - t0
            with lock:
                state["completed"] += 1
                lats.append(lat)
                if alias is not None:
                    sampler.tally(alias, "completed", lat)
            timeline.note("completed", lat)

    from deeplearning_tpu.obs import threads as obs_threads
    pool = [obs_threads.spawn(resolver, daemon=True,
                              name=f"loadgen-resolver-{i}")
            for i in range(8)]
    period = 1.0 / rate_hz
    rng = np.random.default_rng(0)
    t_end = time.perf_counter() + duration_s
    next_t = time.perf_counter()
    while time.perf_counter() < t_end:
        now = time.perf_counter()
        if now < next_t:
            time.sleep(next_t - now)
        next_t += period
        alias, img_pool = sampler.pick(rng)
        img = img_pool[int(rng.integers(len(img_pool)))]
        t0 = time.perf_counter()
        try:
            handle = batcher.submit(img, model=alias)
        except Rejected:
            with lock:
                state["rejected"] += 1
                if alias is not None:
                    sampler.tally(alias, "rejected")
            timeline.note("rejected")
            continue
        with lock:
            state["submitted"] += 1
        timeline.note("submitted")
        handles.put((t0, alias, handle))
    for _ in pool:
        handles.put(None)
    for t in pool:
        t.join(timeout=timeout_s)
    done.set()
    snap = batcher.telemetry.snapshot()
    rec = {"mode": "open", "rate_hz": rate_hz, **state,
           "req_per_s": round(state["completed"] / duration_s, 1),
           **_percentiles_ms(lats),
           "batch_occupancy": snap["batch_occupancy"],
           "queue_depth_mean": snap["queue_depth_mean"],
           "shed_batches": snap["shed_batches"],
           "timeline": timeline.rows()}
    models = sampler.model_recs("open", duration_s)
    if models:
        rec["models"] = models
    return rec


def run_open_loop_http(router, images, rate_hz: float,
                       duration_s: float, timeout_s: float = 10.0,
                       senders: int = 16) -> dict:
    """Open-loop arrivals POSTed to a replica fleet through a
    :class:`~deeplearning_tpu.fleet.FleetRouter` — the drive side of
    the drain-and-requeue choreography. Latency is arrival→response
    (loadgen queueing included: a stalled fleet shows up as p99, not as
    a quietly slower arrival process). 2xx counts as completed, a
    429/503 that survived failover as rejected (an all-shed fleet's
    smallest retry-after hint is surfaced), an empty rotation as
    no_route, connection errors and deadline misses as timed out. Each
    request carries the remaining deadline (``X-Deadline-Ms``); the
    per-second timeline records the router's retry/hedge/deadline-miss
    counts next to the QPS split, and the record embeds
    ``router.resilience_stats()``."""
    import io
    import queue as _queue

    timeline = Timeline()
    jobs: "_queue.Queue" = _queue.Queue()
    lock = threading.Lock()
    state = {"submitted": 0, "completed": 0, "rejected": 0,
             "timed_out": 0, "no_route": 0, "retries": 0, "hedged": 0,
             "deadline_miss": 0}
    hints = []
    lats = []

    def sender():
        while True:
            item = jobs.get()
            if item is None:
                return
            t0, body = item
            code, payload, _url, meta = router.post_ex(
                "/predict", body,
                headers={"Content-Type": "application/octet-stream"},
                deadline_s=timeout_s)
            lat = time.perf_counter() - t0
            retries = int(meta.get("retries", 0))
            with lock:
                state["retries"] += retries
                state["hedged"] += int(bool(meta.get("hedged")))
                state["deadline_miss"] += int(
                    bool(meta.get("deadline_miss")))
                if meta.get("retry_after_s") is not None:
                    hints.append(meta["retry_after_s"])
            if retries:
                timeline.note("retries", n=retries)
            if meta.get("hedged"):
                timeline.note("hedged")
            if meta.get("deadline_miss"):
                timeline.note("deadline_miss")
            if 200 <= code < 300:
                with lock:
                    state["completed"] += 1
                    lats.append(lat)
                timeline.note("completed", lat)
            elif meta.get("no_route"):
                with lock:
                    state["no_route"] += 1
                timeline.note("no_route")
            elif code in (429, 503):
                with lock:
                    state["rejected"] += 1
                timeline.note("rejected")
            else:
                with lock:
                    state["timed_out"] += 1
                timeline.note("timed_out")

    from deeplearning_tpu.obs import threads as obs_threads
    pool = [obs_threads.spawn(sender, daemon=True,
                              name=f"loadgen-http-{i}")
            for i in range(senders)]
    bodies = []
    for img in images[:16]:
        buf = io.BytesIO()
        np.save(buf, img)
        bodies.append(buf.getvalue())
    period = 1.0 / rate_hz
    t_end = time.perf_counter() + duration_s
    next_t = time.perf_counter()
    i = 0
    while time.perf_counter() < t_end:
        now = time.perf_counter()
        if now < next_t:
            time.sleep(next_t - now)
        next_t += period
        with lock:
            state["submitted"] += 1
        timeline.note("submitted")
        jobs.put((time.perf_counter(), bodies[i % len(bodies)]))
        i += 1
    for _ in pool:
        jobs.put(None)
    for t in pool:
        t.join(timeout=timeout_s)
    rec = {"mode": "open_http", "rate_hz": rate_hz, **state,
           "req_per_s": round(state["completed"] / duration_s, 1),
           **_percentiles_ms(lats),
           "failovers": router.failovers,
           "resilience": router.resilience_stats(),
           "timeline": timeline.rows()}
    if hints:
        rec["retry_after_hint_s"] = min(hints)
    return rec


def append_serve_row(results_path: str, rec: dict, **extra) -> None:
    """Append one ``serve_<mode>`` op row ({op, n, ms}, device, UTC time
    and the run's rates) for a run's record to a jsonl."""
    row = {
        "op": f"serve_{rec['mode']}",
        "n": int(rec.get("concurrency", rec.get("rate_hz", 1))),
        "ms": round(float(rec.get("p50_ms", 0.0)), 3),
        "device": jax.devices()[0].device_kind,
        "utc": time.strftime("%Y-%m-%d %H:%M:%S", time.gmtime()),
        "req_per_s": rec.get("req_per_s", 0.0),
        "p99_ms": rec.get("p99_ms", 0.0),
        "completed": rec.get("completed", 0),
        "rejected": rec.get("rejected", 0),
        "batch_occupancy": rec.get("batch_occupancy", 0.0),
        **extra,
    }
    with open(results_path, "a") as f:
        f.write(json.dumps(row) + "\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    # default: a model whose single-request cost is dispatch-dominated,
    # so the compare mode isolates the batching win (a conv model's CPU
    # compute scales linearly with batch and hides the amortization)
    ap.add_argument("--model", default="mnist_fcn")
    ap.add_argument("--num-classes", type=int, default=10)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--size", type=int, default=28)
    ap.add_argument("--buckets", default="1,8,64",
                    help="comma-separated batch buckets")
    ap.add_argument("--mode", default="compare",
                    choices=["closed", "open", "sequential", "compare"])
    ap.add_argument("--concurrency", type=int, default=64)
    ap.add_argument("--n", type=int, default=512,
                    help="total requests (closed/sequential)")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="open-loop arrivals per second")
    ap.add_argument("--duration", type=float, default=5.0,
                    help="open-loop duration seconds")
    ap.add_argument("--max-wait-ms", type=float, default=5.0)
    ap.add_argument("--max-queue", type=int, default=256)
    ap.add_argument("--timeout-s", type=float, default=None,
                    help="per-request deadline")
    ap.add_argument("--results", default=None,
                    help="append serve rows to this jsonl "
                         "(unset or 'none': no file is written)")
    ap.add_argument("--mix", default=None,
                    help='mixed zoo traffic, e.g. "a=0.7,b=0.3": each '
                         "request samples its model by weight "
                         "(closed/open modes; implies a ModelZoo)")
    ap.add_argument("--zoo", default=None,
                    help="tenant specs for --mix aliases: JSON (or "
                         "@file.json) alias -> {model, num_classes, "
                         "image_size, buckets, weight_quant, ...}; "
                         "default: each alias IS its architecture name "
                         "with the CLI's --num-classes/--size")
    ap.add_argument("--fleet-urls", default=None,
                    help="open-loop over HTTP instead of in-process: "
                         "comma-separated replica base URLs routed via "
                         "FleetRouter (round-robin + failover)")
    ap.add_argument("--fleet-dir", default=None,
                    help="like --fleet-urls but discover live replica "
                         "endpoints from this controller run dir on "
                         "every health refresh (scale-ups join, "
                         "drained replicas leave)")
    args = ap.parse_args(argv)
    if args.mix and args.mode not in ("closed", "open"):
        ap.error("--mix needs --mode closed or open")
    if (args.fleet_urls or args.fleet_dir) and args.mode != "open":
        ap.error("--fleet-urls/--fleet-dir need --mode open")
    results_path = args.results
    if results_path and results_path.lower() == "none":
        results_path = None

    if args.fleet_urls or args.fleet_dir:
        from deeplearning_tpu.fleet import FleetRouter
        refresh = None
        urls = []
        if args.fleet_dir:
            from deeplearning_tpu.obs.fleet import discover_endpoints

            def refresh(_dir=args.fleet_dir):
                return discover_endpoints(_dir, live_only=True)
            urls = refresh()
        if args.fleet_urls:
            urls = [u.strip() for u in args.fleet_urls.split(",")
                    if u.strip()]
            refresh = None
        router = FleetRouter(urls, refresh_fn=refresh,
                             timeout_s=args.timeout_s or 10.0)
        rec = run_open_loop_http(
            router, make_images(64, args.size), args.rate,
            args.duration, timeout_s=args.timeout_s or 10.0)
        print(json.dumps(rec), flush=True)
        if results_path:
            append_serve_row(results_path, rec, model=args.model)
        return 0

    from deeplearning_tpu.serve import (InferenceEngine, MicroBatcher,
                                        ModelZoo)

    buckets = tuple(int(b) for b in args.buckets.split(","))

    mix = zoo = None
    images_by_model = {}
    if args.mix:
        mix = parse_mix(args.mix)
        if args.zoo:
            raw = args.zoo
            if raw.startswith("@"):
                with open(raw[1:]) as f:
                    spec = json.load(f)
            else:
                spec = json.loads(raw)
        else:
            spec = {alias: {} for alias in mix}
        zoo = ModelZoo()
        for alias in mix:
            row = dict(spec.get(alias, {}))
            model_name = row.pop("model", alias)
            b = row.pop("buckets", None)
            row["batch_buckets"] = (tuple(int(x) for x in b)
                                    if b else buckets)
            row.setdefault("num_classes", args.num_classes)
            row.setdefault("image_size", args.size)
            zoo.register(
                alias, model_name,
                weight_quant=row.pop("weight_quant", "fp32"),
                max_queue=int(row.pop("max_queue", args.max_queue)),
                default_timeout_s=row.pop("timeout_s", args.timeout_s),
                **row)
        for alias in mix:       # measure serving, not cold loads
            if zoo.load(alias, wait=True) != "warm":
                ap.error(
                    f"tenant {alias!r} failed to load: "
                    f"{zoo.load_errors.get(alias, 'unknown')} — with no "
                    "--zoo spec each --mix alias must BE an architecture "
                    'name (or map it: --zoo \'{"%s": {"model": ...}}\')'
                    % alias)
            images_by_model[alias] = make_images(
                max(buckets[-1], 64), zoo.image_size(alias))
        images = next(iter(images_by_model.values()))
        engine = None
    else:
        engine = InferenceEngine(
            args.model, num_classes=args.num_classes, ckpt=args.ckpt,
            image_size=args.size, batch_buckets=buckets)
        images = make_images(max(buckets[-1], 64), args.size)

    def report(rec, **extra):
        print(json.dumps(rec), flush=True)
        if not results_path:
            return
        models = rec.get("models")
        if models:
            # one op row per tenant, so the per-model latency
            # trajectories land in the results file individually
            for alias, sub in sorted(models.items()):
                append_serve_row(results_path, sub, model=alias,
                                 mix_weight=sub["mix_weight"], **extra)
        else:
            append_serve_row(results_path, rec, model=args.model,
                             **extra)

    def make_batcher():
        kwargs = dict(max_wait_ms=args.max_wait_ms,
                      max_queue=args.max_queue,
                      default_timeout_s=args.timeout_s)
        if zoo is not None:
            return MicroBatcher(zoo=zoo, **kwargs)
        return MicroBatcher(engine, **kwargs)

    recs = []
    if args.mode in ("sequential", "compare"):
        rec = run_sequential(engine, images, args.n)
        report(rec)
        recs.append(rec)
    if args.mode in ("closed", "compare"):
        with make_batcher() as mb:
            rec = run_closed_loop(mb, images, args.concurrency, args.n,
                                  mix=mix,
                                  images_by_model=images_by_model)
        report(rec)
        recs.append(rec)
    if args.mode == "open":
        with make_batcher() as mb:
            rec = run_open_loop(mb, images, args.rate, args.duration,
                                mix=mix,
                                images_by_model=images_by_model)
        report(rec)
        recs.append(rec)
    if args.mode == "compare" and len(recs) == 2:
        speedup = recs[1]["req_per_s"] / max(recs[0]["req_per_s"], 1e-9)
        print(json.dumps({"mode": "compare",
                          "speedup_vs_sequential": round(speedup, 2)}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
