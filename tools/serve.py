#!/usr/bin/env python
"""Inference server CLI over the serving engine.

  # stdin mode: one image path per line, one JSON answer per line
  echo img.png | python tools/serve.py --model mnist_cnn \\
      --num-classes 10 --size 28 [--ckpt DIR]

  # optional HTTP mode (stdlib-only): POST /predict with an .npy body
  python tools/serve.py --model yolox_tiny --num-classes 80 \\
      --size 416 --http 8000

Every request path — stdin lines, HTTP posts, .npz batches — goes
through the same ``MicroBatcher.submit()`` front door, so concurrent
clients batch together, admission control applies (full queue answers
"rejected" with a retry-after hint instead of queueing unboundedly),
and the model only ever executes its warmed bucket shapes. ``GET
/stats`` (HTTP) or EOF (stdin) reports the telemetry snapshot.

Fleet plane: HTTP mode always exposes ``GET /metrics`` (Prometheus
text format, the uniform schema ``obs/fleet.py`` scrapes) and
``GET /metrics.json``; when a supervisor hands down
``DLTPU_ENDPOINT_FILE`` the replica advertises its URL there, and
``DLTPU_TRACE=1`` enables the span tracer with a ``trace.json`` dump on
graceful shutdown (SIGTERM drains the server instead of killing it).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import numpy as np


def load_request_images(path: str, size: int, task: str) -> np.ndarray:
    """One request's model-ready (n, size, size, 3) frames.

    Conventions shared with predict.py/demo.py: ``.npz`` batches are
    model-ready (tools/train.py feeds npz raw — normalizing again would
    double-normalize); image files go through the classification eval
    transform, or plain resize+/255 for detection (demo.py's frame)."""
    from deeplearning_tpu.data.datasets import load_image
    if path.endswith(".npz"):
        imgs = np.load(path)["images"]
    else:
        raw = np.asarray(load_image(path), np.float32)
        if task == "detect":
            if not path.lower().endswith(".npy"):
                raw = raw / 255.0      # .npy is model-ready by convention
            import jax.numpy as jnp
            imgs = np.asarray(jax.image.resize(
                jnp.asarray(raw), (size, size, 3), "bilinear"))[None]
            return imgs.astype(np.float32)
        else:
            from deeplearning_tpu.data.transforms import (
                classification_eval_transform)
            fn = classification_eval_transform((size, size))
            imgs = fn({"image": raw[None]})["image"]
    imgs = np.asarray(imgs, np.float32)
    if imgs.ndim == 3:
        imgs = imgs[None]
    if imgs.shape[1:3] != (size, size):
        import jax.numpy as jnp
        imgs = np.asarray(jax.image.resize(
            jnp.asarray(imgs), (imgs.shape[0], size, size, 3),
            "bilinear"))
    return imgs


def format_answer(task: str, row, names, topk: int) -> dict:
    """One request's JSON answer. Detection answers carry only the
    VALID rows — the fixed-shape class −1 padding slots never leave the
    server."""
    if task == "classify":
        order = np.argsort(-row)[:topk]
        return {"top": [[names.get(int(i), int(i)), round(float(row[i]), 4)]
                        for i in order]}
    keep = np.asarray(row["valid"], bool)
    return {"detections": [
        {"box": [round(float(x), 1) for x in b],
         "score": round(float(s), 4),
         "label": names.get(int(c), int(c))}
        for b, s, c in zip(np.asarray(row["boxes"])[keep],
                           np.asarray(row["scores"])[keep],
                           np.asarray(row["labels"])[keep])]}


def serve_stdin(batcher, task: str, size: int, names, topk: int,
                timeout_s: float, stream_in=None, stream_out=None) -> int:
    """Line protocol: path in, JSON out (one line per image; an .npz
    submits every row concurrently so they micro-batch together)."""
    from deeplearning_tpu.serve import DeadlineExceeded, Rejected
    stream_in = stream_in or sys.stdin
    stream_out = stream_out or sys.stdout
    for line in stream_in:
        path = line.strip()
        if not path:
            continue
        try:
            images = load_request_images(path, size, task)
            handles = [batcher.submit(img) for img in images]
        except Rejected as r:
            print(json.dumps({"error": "rejected", "path": path,
                              "retry_after_s": round(r.retry_after_s, 3)}),
                  file=stream_out, flush=True)
            continue
        except Exception as e:  # noqa: BLE001 - per-line protocol
            print(json.dumps({"error": repr(e), "path": path}),
                  file=stream_out, flush=True)
            continue
        for i, h in enumerate(handles):
            try:
                row = h.result(timeout=timeout_s)
                ans = format_answer(task, row, names, topk)
            except DeadlineExceeded:
                ans = {"error": "deadline_exceeded"}
            ans.update({"path": path, "image": i})
            print(json.dumps(ans), file=stream_out, flush=True)
    print(json.dumps(batcher.telemetry.snapshot()), file=sys.stderr,
          flush=True)
    return 0


_SERVE_COUNTER_NAMES = {
    "submitted": "dltpu_serve_requests_total",
    "completed": "dltpu_serve_completed_total",
    "rejected": "dltpu_serve_rejected_total",
    "timed_out": "dltpu_serve_timed_out_total",
    "batches": "dltpu_serve_batches_total",
    "shed_batches": "dltpu_serve_shed_batches_total",
}
_SERVE_GAUGE_KEYS = (
    "requests_per_s", "rejects_per_s", "completions_per_s", "window_s",
    "batch_occupancy", "queue_depth_mean", "e2e_ms_p50", "e2e_ms_p90",
    "e2e_ms_p99", "dispatch_ms_p50", "dispatch_ms_p90",
    "dispatch_ms_p99")


def _mirror_telemetry(reg, snap, labels=None):
    for key, name in _SERVE_COUNTER_NAMES.items():
        reg.counter(name, f"serve telemetry {key}",
                    labels=labels).set_total(snap.get(key, 0.0))
    for key in _SERVE_GAUGE_KEYS:
        if key in snap:
            reg.gauge(f"dltpu_serve_{key}", f"serve telemetry {key}",
                      labels=labels).set(snap[key])


def make_metrics_collector(batcher):
    """Scrape-time pull adapter: mirror ``ServeTelemetry.snapshot()``
    (rates, percentiles, cumulative counts) and ``engine.stats()`` into
    the registry under the ``dltpu_serve_*`` names ``obs/fleet.py``
    rolls up. Counters use ``set_total`` (monotonic mirror); xla-side
    compile/HBM metrics are PUSHED by obs.xla and deliberately not
    mirrored here — one writer per metric, never two.

    Zoo mode additionally mirrors every tenant lane under the SAME
    metric names with a ``model`` label (the per-tenant series
    ``fleet.compute_rollup`` folds into its ``models`` section) plus
    per-model queue/warm gauges and the zoo residency counters."""

    def _collect(reg):
        snap = batcher.telemetry.snapshot()
        _mirror_telemetry(reg, snap)
        reg.gauge("dltpu_serve_queue_depth",
                  "live micro-batch queue depth").set(
            float(batcher.queue_depth))
        reg.gauge("dltpu_serve_standby",
                  "1 while a warm spare out of rotation").set(
            1.0 if batcher.standby else 0.0)
        if batcher.zoo is None:
            for key, val in batcher.engine.stats().items():
                if isinstance(val, (int, float)) \
                        and not isinstance(val, bool):
                    safe = "".join(c if c.isalnum() else "_"
                                   for c in key)
                    reg.gauge(f"dltpu_engine_{safe}",
                              f"engine stats {key}").set(float(val))
            return
        zs = batcher.zoo.stats()
        for key in ("registered", "resident", "loads", "evictions",
                    "rejected_loads"):
            reg.gauge(f"dltpu_zoo_{key}",
                      f"zoo {key}").set(float(zs[key]))
        for alias, row in zs["models"].items():
            labels = {"model": alias}
            lane_tel = batcher.lane_telemetry(alias)
            if lane_tel is not None:
                _mirror_telemetry(reg, lane_tel.snapshot(), labels)
            reg.gauge("dltpu_serve_queue_depth",
                      "live micro-batch queue depth",
                      labels=labels).set(
                float(batcher.lane_depth(alias)))
            reg.gauge("dltpu_zoo_model_warm", "1 while servable",
                      labels=labels).set(1.0 if row["warm"] else 0.0)
            reg.gauge("dltpu_serve_brownout_step",
                      "tenant degrade-ladder step (0 = full service)",
                      labels=labels).set(
                float(batcher.brownout_step(alias)))
            reg.gauge("dltpu_zoo_model_bytes", "resident weight bytes",
                      labels=labels).set(float(row["bytes"]))
            if "trace_count" in row:
                reg.gauge("dltpu_zoo_model_trace_count",
                          "engine trace count", labels=labels).set(
                    float(row["trace_count"]))
    return _collect


def serve_http(batcher, task: str, size: int, names, topk: int,
               timeout_s: float, port: int,
               wedge_deadline_s: float = 30.0):
    """Minimal stdlib HTTP front: POST /predict (.npy body, one image or
    a batch) → JSON; GET /stats → telemetry; GET /healthz → the health
    verdict, including the DispatchWatch wedge check (requests queued
    while the dispatch counter is frozen past ``wedge_deadline_s`` →
    503 with ``"wedged": true``, so a balancer drains a stuck replica
    the process itself cannot notice); GET /metrics + /metrics.json →
    the fleet scrape surface. ThreadingHTTPServer gives each request
    its own thread, so concurrent posts micro-batch.

    Zoo mode (``batcher.zoo`` set) adds the multi-tenant surface:
    ``POST /predict/<model>`` routes to that tenant's lane (a cold
    tenant hot-loads in the background; HBM-pressure refusals answer
    429 with the model and reason in the body), ``GET /models`` dumps
    the per-tenant state table, and ``POST /admin/load/<model>`` /
    ``POST /admin/evict/<model>`` drive residency by hand."""
    import io
    from concurrent.futures import TimeoutError as FutureTimeout
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from deeplearning_tpu.obs import metrics as obs_metrics
    from deeplearning_tpu.obs import xla as obs_xla
    from deeplearning_tpu.serve import DeadlineExceeded, Rejected
    from deeplearning_tpu.serve.health import DispatchWatch
    from deeplearning_tpu.serve.health import health as health_check
    from deeplearning_tpu.serve.health import zoo_health

    zoo = batcher.zoo
    watch = DispatchWatch(batcher, wedge_deadline_s)
    registry = obs_metrics.enable()
    registry.register_collector(make_metrics_collector(batcher))

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):   # quiet: telemetry is the log
            pass

        def _json(self, code: int, payload: dict):
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def _rejected(self, r):
            # admission backpressure answers 429 ("slow down, retry
            # here"); a standby or chaos-injected refusal answers 503
            # ("wrong replica / failed attempt") so the router's
            # breaker classification sees the difference
            code = 503 if r.reason in ("standby", "injected") else 429
            body = json.dumps({
                "error": "rejected", "reason": r.reason,
                "model": r.model, "depth": r.depth,
                "retry_after_s": round(r.retry_after_s, 3)}).encode()
            self.send_response(code)
            self.send_header("Retry-After", f"{r.retry_after_s:.3f}")
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            route = self.path.rstrip("/")
            if route == "/stats":
                payload = batcher.telemetry.snapshot()
                if zoo is None:
                    payload["engine"] = batcher.engine.stats()
                else:
                    payload["zoo"] = zoo.stats()
                payload["compile"] = obs_xla.compile_stats()
                payload["hbm"] = obs_xla.hbm_snapshot()
                return self._json(200, payload)
            if route == "/models" and zoo is not None:
                return self._json(200, zoo.stats())
            if route == "/healthz":
                if zoo is None:
                    code, payload = health_check(batcher.engine, batcher,
                                                 wedge=watch)
                else:
                    code, payload = zoo_health(zoo, batcher, wedge=watch)
                payload.update(obs_metrics.replica_identity())
                return self._json(code, payload)
            if route == "/metrics":
                body = registry.prometheus_text().encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4; "
                                 "charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return None
            if route == "/metrics.json":
                return self._json(200, registry.snapshot())
            return self._json(404, {"error": "GET /stats, /healthz, "
                                             "/metrics or /metrics.json"})

        def _predict(self, alias):
            n = int(self.headers.get("Content-Length", 0))
            # end-to-end deadline: a router stamping X-Deadline-Ms is
            # spending ONE budget across retries/hedges — map it onto
            # the admission deadline so queue time counts against it
            req_timeout = timeout_s
            hdr = self.headers.get("X-Deadline-Ms")
            if hdr:
                try:
                    req_timeout = min(timeout_s,
                                      max(int(hdr), 1) / 1e3)
                except ValueError:
                    pass
            try:
                arr = np.load(io.BytesIO(self.rfile.read(n)),
                              allow_pickle=False)
                images = np.asarray(arr, np.float32)
                if images.ndim == 3:
                    images = images[None]
                handles = [batcher.submit(img, timeout_s=req_timeout,
                                          model=alias)
                           for img in images]
                rows = [h.result(timeout=req_timeout) for h in handles]
            except Rejected as r:
                return self._rejected(r)
            except (DeadlineExceeded, FutureTimeout):
                return self._json(504, {"error": "deadline_exceeded"})
            except KeyError as e:
                return self._json(404, {"error": repr(e)})
            except Exception as e:  # noqa: BLE001 - request-scoped
                return self._json(400, {"error": repr(e)})
            if zoo is None:
                row_task = task
            else:
                # the engine served the batch, so it was warm a moment
                # ago; a racing evict just means we format as classify
                eng = zoo.engine(alias or zoo.models()[0])
                row_task = eng.task if eng is not None else "classify"
            return self._json(200, {"results": [
                format_answer(row_task, row, names, topk)
                for row in rows]})

        def do_POST(self):
            parts = [p for p in self.path.split("/") if p]
            if parts and parts[0] == "predict":
                if len(parts) == 1:
                    return self._predict(None)
                if len(parts) == 2 and zoo is not None:
                    return self._predict(parts[1])
            elif parts == ["admin", "drain"]:
                # fleet controller verb: stop accepting, finish lanes.
                # healthz flips to 503 "draining" so routers reroute;
                # the controller polls "drained" before the requeue
                batcher.drain()
                return self._json(200, {"draining": True,
                                        "drained": bool(batcher.drained),
                                        "queue_depth":
                                            batcher.queue_depth})
            elif parts == ["admin", "promote"]:
                # fleet controller verb: warm standby -> rotation. The
                # engine AOT'd at startup, so this is a flag flip —
                # healthz answers "ready" on the very next probe
                return self._json(200, {"promoted": batcher.promote(),
                                        "standby": batcher.standby})
            elif (len(parts) == 4 and parts[0] == "admin"
                    and parts[1] == "brownout"):
                # fleet controller verb: one tenant's degrade-ladder
                # step (0 restores). Step 2+ additionally demotes the
                # tenant to int8 residency when a zoo owns the weights
                alias, step_s = parts[2], parts[3]
                try:
                    step = int(step_s)
                except ValueError:
                    return self._json(400,
                                      {"error": "step must be an int"})
                applied = batcher.set_brownout(alias, step)
                out = {"model": alias, "step": applied}
                if zoo is not None and applied >= 2:
                    out["demoted"] = zoo.demote_residency(alias)
                return self._json(200, out)
            elif (zoo is not None and len(parts) == 3
                    and parts[0] == "admin"
                    and parts[1] in ("load", "evict")):
                verb, alias = parts[1], parts[2]
                try:
                    if verb == "load":
                        state = zoo.load(alias, wait=False)
                    else:
                        evicted = zoo.evict(alias)
                        state = zoo.state(alias)
                except Rejected as r:
                    return self._rejected(r)
                except KeyError as e:
                    return self._json(404, {"error": repr(e)})
                out = {"model": alias, "state": state}
                if verb == "evict":
                    out["evicted"] = evicted
                return self._json(200, out)
            return self._json(404, {
                "error": "POST /predict[/<model>], /admin/drain, "
                         "/admin/promote, "
                         "/admin/brownout/<model>/<step> or "
                         "/admin/{load,evict}/<model>"})

    server = ThreadingHTTPServer(("127.0.0.1", port), Handler)
    url = f"http://127.0.0.1:{server.server_port}"
    # advertise the scrape endpoint when a supervisor asked for it
    obs_metrics.write_endpoint(url, role="serve")
    endpoints = ["/predict", "/stats", "/healthz", "/metrics",
                 "/metrics.json", "/admin/drain", "/admin/promote",
                 "/admin/brownout/<model>/<step>"]
    if zoo is not None:
        endpoints[:1] = ["/predict/<model>", "/models",
                         "/admin/load/<model>", "/admin/evict/<model>"]
    print(json.dumps({"serving": url, "endpoints": endpoints}),
          flush=True)
    return server


def parse_zoo_spec(raw: str) -> dict:
    """``--zoo`` value: inline JSON or ``@file.json`` mapping alias →
    tenant spec. Per-tenant keys: ``model`` (architecture name,
    defaults to the alias), policy keys (``weight_quant``,
    ``max_queue``, ``shed_threshold``, ``timeout_s``, ``est_bytes``,
    ``preload``), ``buckets`` (list), and everything else passes
    through as engine kwargs (``num_classes``, ``image_size``,
    ``ckpt``, ...)."""
    if raw.startswith("@"):
        with open(raw[1:]) as f:
            spec = json.load(f)
    else:
        spec = json.loads(raw)
    if not isinstance(spec, dict) or not spec:
        raise ValueError("--zoo must map alias -> tenant spec")
    return spec


def build_zoo(spec: dict, args):
    """ModelZoo from a parsed ``--zoo`` spec + CLI defaults."""
    from deeplearning_tpu.serve import ModelZoo
    zoo = ModelZoo(alert_frac=args.hbm_alert_frac,
                   max_resident=args.max_resident)
    preload = []
    for alias, row in spec.items():
        row = dict(row)
        model_name = row.pop("model", alias)
        if row.pop("preload", False):
            preload.append(alias)
        buckets = row.pop("buckets", None)
        if buckets is not None:
            row["batch_buckets"] = tuple(int(b) for b in buckets)
        row.setdefault("batch_buckets", tuple(
            int(b) for b in args.buckets.split(",")))
        zoo.register(
            alias, model_name,
            weight_quant=row.pop("weight_quant", "fp32"),
            max_queue=int(row.pop("max_queue", args.max_queue)),
            shed_threshold=row.pop("shed_threshold", None),
            default_timeout_s=row.pop("timeout_s", args.timeout_s),
            est_bytes=row.pop("est_bytes", None),
            **row)
    for alias in preload:
        zoo.load(alias, wait=True)
    return zoo


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default=None,
                    help="single-model mode: architecture to serve")
    ap.add_argument("--zoo", default=None,
                    help="multi-tenant mode: JSON (or @file.json) "
                         "mapping alias -> tenant spec; see "
                         "parse_zoo_spec")
    ap.add_argument("--max-resident", type=int, default=None,
                    help="zoo: cap on simultaneously-warm models")
    ap.add_argument("--hbm-alert-frac", type=float, default=None,
                    help="zoo: evict when a load projects past this "
                         "HBM fraction (default DLTPU_HBM_ALERT_FRAC "
                         "or 0.9)")
    ap.add_argument("--num-classes", type=int, default=10)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--size", type=int, default=224)
    ap.add_argument("--buckets", default="1,8,32",
                    help="comma-separated batch buckets")
    ap.add_argument("--max-wait-ms", type=float, default=5.0)
    ap.add_argument("--max-queue", type=int, default=256)
    ap.add_argument("--timeout-s", type=float, default=30.0,
                    help="per-request deadline")
    ap.add_argument("--topk", type=int, default=5)
    ap.add_argument("--score", type=float, default=0.3,
                    help="detection score threshold")
    ap.add_argument("--max-det", type=int, default=100)
    ap.add_argument("--nms-impl", default="auto")
    ap.add_argument("--tta", action="store_true",
                    help="classification flip-TTA inside the executable")
    ap.add_argument("--classes", default=None,
                    help="json mapping class index -> name")
    ap.add_argument("--http", type=int, default=None,
                    help="serve HTTP on this port instead of stdin "
                         "(0 = ephemeral)")
    ap.add_argument("--wedge-deadline-s", type=float, default=30.0,
                    help="healthz reports wedged after this many seconds "
                         "of queued-but-frozen dispatch")
    return ap


def build_engine(args):
    """Single-model mode's warmed ``InferenceEngine`` from parsed CLI
    args (every bucket AOT-compiled before this returns)."""
    from deeplearning_tpu.serve import InferenceEngine
    return InferenceEngine(
        args.model, num_classes=args.num_classes, ckpt=args.ckpt,
        image_size=args.size,
        batch_buckets=tuple(int(b) for b in args.buckets.split(",")),
        tta=args.tta, score_thresh=args.score, max_det=args.max_det,
        nms_impl=args.nms_impl)


def build_batcher(args, engine=None, zoo=None, heartbeat=None):
    """The request front door every serve path shares (not started:
    use it as a context manager)."""
    from deeplearning_tpu.serve import MicroBatcher
    return MicroBatcher(engine, zoo=zoo,
                        max_wait_ms=args.max_wait_ms,
                        max_queue=args.max_queue,
                        default_timeout_s=args.timeout_s,
                        heartbeat=heartbeat,
                        standby=os.environ.get("DLTPU_STANDBY") == "1")


def main(argv=None) -> int:
    ap = build_parser()
    args = ap.parse_args(argv)
    if (args.model is None) == (args.zoo is None):
        ap.error("pass exactly one of --model or --zoo")
    if args.zoo is not None and args.http is None:
        ap.error("--zoo requires --http (stdin mode is single-model)")

    from deeplearning_tpu.analysis import strict as strict_mod
    from deeplearning_tpu.elastic import heartbeat as hb
    from deeplearning_tpu.obs import spans

    # DLTPU_STRICT=threads: instrument the fleet's locks BEFORE the
    # zoo/batcher/heartbeat objects below create them
    strict_mod.maybe_enable_threads(strict_mod.resolve())

    # DLTPU_TRACE=1: record the span timeline and dump trace.json on
    # graceful exit (next to the endpoint file when supervised, so
    # tools/trace_merge.py finds one trace per replica workdir)
    trace_path = None
    if os.environ.get("DLTPU_TRACE"):
        spans.enable()
        ep = os.environ.get("DLTPU_ENDPOINT_FILE")
        trace_path = os.environ.get("DLTPU_TRACE_FILE") or os.path.join(
            os.path.dirname(ep) if ep else ".", "trace.json")

    engine = zoo = None
    if args.zoo is not None:
        zoo = build_zoo(parse_zoo_spec(args.zoo), args)
        print(json.dumps({"ready": zoo.stats()}), file=sys.stderr,
              flush=True)
        task, size = "classify", 0     # resolved per model per request
    else:
        engine = build_engine(args)
        print(json.dumps({"ready": engine.stats()}), file=sys.stderr,
              flush=True)
        task, size = engine.task, args.size
    names = {}
    if args.classes:
        with open(args.classes) as f:
            names = {int(k): v for k, v in json.load(f).items()}

    # supervised serving: when DLTPU_HEARTBEAT names a file (the
    # supervisor's contract with its children), the batcher's dispatch
    # loop advances the activity watermark — a wedged replica gets the
    # same SIGTERM/requeue treatment as a wedged training run
    beat = writer = None
    beat_path = os.environ.get(hb.ENV_VAR)
    if beat_path:
        beat = hb.Heartbeat()
        writer = hb.HeartbeatWriter(beat_path, beat).start()
    try:
        with build_batcher(args, engine, zoo, beat) as batcher:
            if args.http is not None:
                server = serve_http(batcher, task, size,
                                    names, args.topk, args.timeout_s,
                                    args.http, args.wedge_deadline_s)

                # SIGTERM (the supervisor's drain signal) shuts the
                # server down from a helper thread — serve_forever
                # returns, the trace dumps, the heartbeat finalizes —
                # instead of the default die-mid-request
                import signal

                from deeplearning_tpu.elastic.preempt import \
                    EXIT_PREEMPTED
                from deeplearning_tpu.obs import flight as obs_flight
                from deeplearning_tpu.obs import threads as obs_threads

                rc_holder = {"rc": 0}

                def _drain(signum, frame):
                    obs_threads.spawn(server.shutdown,
                                      name="serve-drain",
                                      daemon=True)
                try:
                    signal.signal(signal.SIGTERM, _drain)
                except ValueError:
                    pass           # non-main thread (embedded use)

                # preemption (injected via preempt_replica:<i>, or a
                # platform eviction the batcher surfaces): drain, shut
                # down gracefully, and exit 75 so the supervisor
                # classifies capacity-loss — not a crash, not a clean
                # completion
                def _preempted():
                    rc_holder["rc"] = EXIT_PREEMPTED
                    obs_flight.record("serve_preempted",
                                      dispatched=batcher.dispatched)
                    batcher.drain()
                    obs_threads.spawn(server.shutdown,
                                      name="serve-preempt-drain",
                                      daemon=True)
                batcher.on_preempt = _preempted

                # chaos crash (crash_replica:<i>): a hard, instant
                # death — no drain, no cleanup; the supervisor must
                # classify a crash and in-flight clients see the
                # connection drop, exactly like a segfaulted replica
                def _crashed():
                    obs_flight.record("serve_crash",
                                      dispatched=batcher.dispatched)
                    os._exit(1)
                batcher.on_crash = _crashed
                try:
                    server.serve_forever()
                except KeyboardInterrupt:
                    pass
                finally:
                    server.server_close()
                return rc_holder["rc"]
            return serve_stdin(batcher, task, size, names,
                               args.topk, args.timeout_s)
    finally:
        if trace_path is not None:
            tracer = spans.get_tracer()
            if tracer is not None:
                tracer.dump(trace_path)
        if writer is not None:
            writer.stop()


if __name__ == "__main__":
    raise SystemExit(main())
