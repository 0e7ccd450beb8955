"""The token driver: what ``drivers/train.py`` does for images, for rows of
token ids and a language model. ``tools/train.py::build_trainer`` ->
``Trainer.train``, the path a user's run takes, with the feed running
(``data.npz`` -> ``ArraySource`` -> ``DataLoader`` -> ``DevicePrefetcher``).

The set is ``dataset_rows`` rows of ``seq_len + 1`` int32 ids uniform over the
vocabulary held, from ``--seed``, cycled inside one Trainer epoch. Set-up
builds one trainer, hands it the benchmark's weights and drives
``Trainer.train``: ``warmup_steps`` steps waited for one by one (the first
``followed_steps`` are what the reference follows afterwards), then the window,
closed where the steps in flight carry the device to ``--seconds``. A step is
most of a second here and the runtime lets the host run 32 steps ahead (24 s of
work), so inside the window the host waits for the step three behind the one
it has just dispatched: two steps are always queued behind the running one, the
device never waits, and the window ends within a step of ``--seconds``. One item is one row: the rate is in
sequences a second (``tokens_per_s`` is among the facts). The expert layers'
counters (rows sent to held experts, largest over mean load) are read from the
step's own metrics over the window, without waiting for them inside it.
"""

from __future__ import annotations

import collections
import gc
import glob
import os
import shutil
import statistics
import sys
import time

import numpy as np

from benchmarks.drivers.train import (_CloseWindow, _hook, _memory_peak,
                                      _moments)
from benchmarks.harness import check, spec
from benchmarks.harness.trace import Trace, find_xplane, op_paths
from benchmarks.references import train_ref, train_ref_lm

KEY_IDS = 16      # a row is recognised by its first ids
IN_FLIGHT = 3     # steps dispatched and not waited for inside the window


def token_set(seed: int, rows: int, seq_len: int, vocab: int) -> np.ndarray:
    """(rows, seq_len + 1) int32 ids uniform over the vocabulary; every row
    differs from every other in its first ids."""
    tokens = np.random.default_rng([seed, rows, seq_len]).integers(
        0, vocab, (rows, seq_len + 1), dtype=np.int32)
    if len({row[:KEY_IDS].tobytes() for row in tokens}) != rows:
        raise ValueError("two generated rows share their first ids")
    return tokens


def write_npz(directory: str, stem: str, seed: int, tokens) -> str:
    """One seed's set on disk at a time, as the program's ``data.npz``."""
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, f"{stem}_{seed}.npz")
    for old in glob.glob(os.path.join(directory, f"{stem}_*.npz")):
        if old != path:
            os.remove(old)
    if not os.path.exists(path):
        tmp = path + ".tmp.npz"
        np.savez(tmp, tokens=tokens)
        os.replace(tmp, path)
    return path


def match_rows(batch: np.ndarray, tokens: np.ndarray, index: dict) -> dict:
    """Which rows of the set the feed delivered, judged by content: every
    delivered row has to be one row of the set, id for id, and none twice."""
    ids = [index.get(row[:KEY_IDS].tobytes(), -1) for row in batch]
    good = [i for i in ids if i >= 0]
    wrong = len(ids) - len(good) + (len(good) - len(set(good)))
    if not wrong:
        wrong = int(np.sum(np.any(tokens[ids] != batch, axis=1)))
    return {"ids": ids, "wrong": wrong,
            "tokens": tokens[ids] if not wrong else None}


def compare(program: dict, reference: dict) -> dict:
    """``harness/check.py``'s numbers without a moving average: the losses,
    the gradient norms, the first gradient and the parameters' change by leaf
    (worst and median)."""
    grads, leaves = reference["first_grad"], reference["first_grad_leaves"]
    rms = [g / max(x.size, 1) ** 0.5 for g, x in zip(grads, leaves)]
    floor = check.NEGLIGIBLE_GRADIENT * statistics.median(rms)
    grad = check._leaf_gaps(program["first_grad"], grads)
    change = check._change_gaps(program["change"], reference["change"],
                                leaves, floor)
    return {
        "loss_gap": max(abs(p - r) / abs(r) for p, r in
                        zip(program["loss"], reference["loss"])),
        "grad_norm_gap": max(abs(p - r) / r for p, r in
                             zip(program["grad_norm"], reference["grad_norm"])),
        "grad_leaf_gap": max(grad),
        "grad_median_leaf_gap": statistics.median(grad),
        "change_leaf_gap": max(change),
        "change_median_leaf_gap": statistics.median(change),
    }


def _counters(held: list) -> dict:
    """The window's expert counters from the steps' metrics (fetched after
    the window): rows the router sent to held experts in all, the executions
    of an expert layer they are spread over, and the mean over steps and
    layers of the largest expert's load over the mean load."""
    import jax
    held = jax.device_get(held)
    rows = [v for m in held for k, v in m.items()
            if k.startswith("moe/rows_held/")]
    loads = [v for m in held for k, v in m.items()
             if k.startswith("moe/load_max_over_mean/")]
    if not rows:
        return {}
    return {"expert_rows": float(np.sum(rows)),
            "expert_layer_steps": len(rows),
            "expert_load_max_over_mean": float(np.mean(loads))}


def _on_device(device) -> str:
    """What the device holds, for the lines that say where memory went."""
    import jax
    stats = device.memory_stats() or {}
    return (f"arrays {sum(x.nbytes for x in jax.live_arrays()) / 1e6:.1f} MB, "
            + ", ".join(f"{k} {stats[k] / 1e6:.1f} MB" for k in (
                "bytes_in_use", "bytes_reserved", "peak_bytes_in_use",
                "peak_bytes_reserved", "largest_free_block_bytes",
                "bytes_limit") if k in stats))


def run(run) -> dict:
    import jax

    sys.path[:0] = [p for p in (spec.ROOT, os.path.join(spec.ROOT, "tools"))
                    if p not in sys.path]
    try:
        import train as train_cli
        from deeplearning_tpu.core.config import config_cli
        from deeplearning_tpu.obs import spans
        from deeplearning_tpu.obs.xla import compile_events
        from deeplearning_tpu.core.registry import MODELS
    except ImportError as exc:
        raise spec.SpecError(f"the program is not in this checkout: {exc}")
    traffic, config = run.traffic, run.config
    shapes, recipe = config["shapes"], config["recipe"]
    if config["registry_name"] not in MODELS:
        raise spec.SpecError(f"the program has no model "
                             f"{config['registry_name']!r}")

    marks = [("start", run.t_start), ("imports", time.perf_counter())]

    def mark(name):
        marks.append((name, time.perf_counter()))

    fam = train_ref.family(config["family"])
    gb, warm, followed = (traffic["global_batch"], traffic["warmup_steps"],
                          traffic["followed_steps"])
    if not 0 < followed <= warm:
        raise spec.SpecError("followed_steps must lie within warmup_steps")

    # ---- inputs from the seed
    tokens = token_set(run.seed, traffic["dataset_rows"], shapes["seq_len"],
                       shapes["vocab_size"])
    npz = write_npz(os.path.join(run.cache_dir, "data"),
                    f"tokens_{traffic['dataset_rows']}x{shapes['seq_len']}",
                    run.seed, tokens)
    mark("data")
    argv = [a.replace("{root}", spec.ROOT) for a in config["argv"]] \
        + list(traffic["argv"]) + [f"data.npz={npz}",
                                   f"data.global_batch={gb}",
                                   f"train.seed={run.seed}"]
    trainer = train_cli.build_trainer(config_cli(train_cli.Config(), argv),
                                      devices=run.devices)
    trainer.epochs = trainer.eval_every = 10 ** 9   # no eval inside the window
    feed = trainer.train_loader
    loader = _hook(feed, "loader", "to cycle the rows without epoch ends")
    _hook(loader, "infinite", "to cycle the rows without epoch ends")
    loader.infinite = True
    _hook(feed, "reseed", "to restart the feed on the endless loader")(0)
    aot_text = _hook(_hook(trainer, "_aot_step", "for the compiled step's text"),
                     "as_text", "for the op paths of the traced run")
    _hook(trainer, "_batches", "to stop the feed's threads after the window")
    mark("build_trainer")

    # ---- the benchmark's weights, in the program's tree; the program's own
    # draw is dropped first and the start is kept on the host, so that the
    # device never holds more than the program's state
    theirs = trainer.state.params
    shardings = jax.tree.map(lambda x: x.sharding, theirs)
    shapes_theirs = jax.tree.map(lambda x: x.shape, theirs)
    paths_theirs = train_ref.leaf_paths(theirs)
    del theirs
    trainer.state = trainer.state.replace(params=None)
    start = fam.make_params(fam.param_spec(shapes), run.seed)
    if shapes_theirs != jax.tree.map(lambda x: x.shape, start):
        raise spec.SpecError(
            "the configuration's file and the program disagree on the parameters: "
            + str(sorted(set(paths_theirs) ^ set(train_ref.leaf_paths(start)))[:8]))
    start_host = jax.tree.leaves(jax.device_get(start))
    trainer.state = trainer.state.replace(params=jax.device_put(start, shardings))
    del start
    if run.sabotage is not None:
        run.sabotage(trainer)
    mark("weights")

    st = {"n": 0, "steps": 0, "rows": [], "loss": [], "grad_norm": [],
          "feed_wait": 0.0, "open": None, "start": start_host, "before": [],
          "after": [], "pending": collections.deque(), "done": 0, "moe": [],
          "done_at": []}
    trace_dir = os.path.join(run.cache_dir, "trace")

    def before(tr, batch):
        if st["n"] < followed:
            st["rows"].append(np.asarray(batch["tokens"]))
        wait = getattr(tr.train_loader, "last_data_wait", None)
        if st["open"] is not None:
            st["feed_wait"] += wait or 0.0
            st["before"].append(time.perf_counter())

    def after(tr, metrics):
        st["n"] += 1
        n = st["n"]
        if n > warm:
            st["steps"] += 1
            st["after"].append(time.perf_counter())
            st["moe"].append({k: v for k, v in metrics.items()
                              if k.startswith("moe/")})
            # The image driver's rule (look, without waiting, at which steps
            # are done) closed this cell's window at 25-30 s: the host had
            # dispatched 32 steps, 24 s of work, before two were done (my
            # chip runs, PR 32). So the host waits for the step IN_FLIGHT
            # behind, and closes once the steps in flight will carry the
            # device to --seconds.
            st["pending"].append(metrics["loss"])
            while len(st["pending"]) > IN_FLIGHT:
                jax.block_until_ready(st["pending"].popleft())
                st["done"] += 1
                st["done_at"].append(time.perf_counter() - st["open"])
            elapsed = time.perf_counter() - st["open"]
            ahead = len(st["pending"]) * elapsed / st["done"] \
                if st["done"] else 0.0
            if elapsed + ahead >= run.seconds:
                raise _CloseWindow
            return
        jax.block_until_ready(tr.state)
        if n == 1:
            mark("first_step")
        if n <= followed:
            st["loss"].append(float(metrics["loss"]))
            st["grad_norm"].append(float(metrics["grad_norm"]))
        if n == 1:
            st["first_grad"] = [float(x) / (1.0 - recipe["b1"]) for x in
                                train_ref.leaf_norms(_moments(tr.state.opt_state))]
        if n == followed:
            st["change"] = [np.asarray(a, np.float32) - b for a, b in zip(
                jax.tree.leaves(jax.device_get(tr.state.params)), st["start"])]
            st["start"] = None
        if n == warm:
            if run.traced:
                # device events only, the program's spans from its own ring
                st["ring"] = spans.enable()
                st["ring"].clear()
                shutil.rmtree(trace_dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            mark("warmup_steps")
            st["open"] = time.perf_counter()

    trainer.callbacks.register("before_iter", before)
    trainer.callbacks.register("after_iter", after)
    del start_host
    try:
        trainer.train()
        raise RuntimeError("Trainer.train returned before the window closed")
    except _CloseWindow:
        pass
    jax.block_until_ready(trainer.state)
    t_close = time.perf_counter()
    host_events = []
    if run.traced:
        jax.profiler.stop_trace()
        host_events = [e for e in st["ring"].events() if e.get("ph") == "X"]
        spans.disable()
    window_s = t_close - st["open"]
    print("setup: " + ", ".join(f"{b[0]} {b[1] - a[1]:.2f}" for a, b in
                                zip(marks, marks[1:])), file=sys.stderr)
    print(f"window: {st['steps']} steps of {gb} rows of {shapes['seq_len']} "
          f"tokens in {window_s:.3f} s, {trainer.epoch} epoch boundaries; last "
          f"dispatch at {st['after'][-1] - st['open']:.3f} s, drained "
          f"{t_close - st['after'][-1]:.3f} s later; steps waited for were done "
          f"at {[round(t, 2) for t in st['done_at']]} s", file=sys.stderr,
          flush=True)

    items = st["steps"] * gb
    facts = {
        "setup_s": st["open"] - run.t_start,
        "window_s": window_s, "steps": st["steps"], "batch": gb,
        "items": items, "epoch_boundaries": trainer.epoch,
        "tokens_per_s": items * shapes["seq_len"] / window_s,
        "feed": {"data_wait_s": st["feed_wait"]},
        "compile_events": [dict(e) for e in compile_events()],
        "memory_peak_bytes": _memory_peak(run.devices, run.peaks is not None),
        **_counters(st["moe"]),
    }
    hlo_text = aot_text() if run.traced else ""

    # ---- free the program's state and its executables, then the reference.
    # The buffers are deleted, not just dropped (the trainer stays reachable:
    # its health callback is registered with the metrics server), and the
    # compiled steps are unloaded: a loaded step keeps its 5.45 GB of
    # temporaries reserved, and with either left the reference found 0.4 GB
    # free of 16 (my chip runs, PR 32)
    _hook(trainer._batches, "close", "to stop the feed's threads")()
    for leaf in jax.tree.leaves((trainer.state, st["moe"],
                                 list(st["pending"]))):
        leaf.delete()
    trainer.callbacks = trainer.state = None
    trainer._aot_step = trainer.train_step = trainer.eval_step = None
    st["moe"] = st["pending"] = None
    del trainer, aot_text
    gc.collect()
    jax.clear_caches()
    gc.collect()
    print("program phases: " + ", ".join(
        f"{e['name']} {e['seconds']:.2f}" for e in spans.phases())
        + "; after the program: " + _on_device(run.devices[0]),
        file=sys.stderr, flush=True)

    if run.traced:
        xplane = find_xplane(trace_dir)
        run.trace = Trace.from_file(xplane)
        run.trace.place_host_spans(
            host_events, traffic.get("host_spans", ()), anchor="dispatch")
        run.op_paths = op_paths(hlo_text)
        if not run.op_paths:
            raise spec.SpecError("the compiled step's text names no op path")
        if run.keep_dir:
            os.makedirs(run.keep_dir, exist_ok=True)
            shutil.copy(xplane, os.path.join(run.keep_dir, "trace.xplane.pb"))
            with open(os.path.join(run.keep_dir, "step.hlo.txt"), "w") as f:
                f.write(hlo_text)
        shutil.rmtree(trace_dir, ignore_errors=True)

    t_ref = time.perf_counter()
    index = {row[:KEY_IDS].tobytes(): i for i, row in enumerate(tokens)}
    matched = [match_rows(rows, tokens, index) for rows in st["rows"]]
    rows_wrong = sum(m["wrong"] for m in matched)
    program = {k: st[k] for k in ("loss", "grad_norm", "first_grad", "change")}
    if rows_wrong:
        numbers = {"rows_wrong": float(rows_wrong)}
    else:
        batches = [m["tokens"] for m in matched]
        again = fam.make_params(fam.param_spec(shapes), run.seed)
        jax.block_until_ready(again)
        print("with the reference's weights: " + _on_device(run.devices[0]),
              file=sys.stderr, flush=True)
        try:
            ref = train_ref_lm.follow(
                fam_name=config["family"], shapes=shapes, recipe=recipe,
                params=again, batches=batches, rows=traffic["reference_rows"])
        except Exception:
            print("the reference failed with, on the device: "
                  + _on_device(run.devices[0]), file=sys.stderr, flush=True)
            raise
        numbers = compare(program, ref)
        numbers["rows_wrong"] = 0.0
        run.reference, run.reference_inputs = ref, batches
    run.program = program
    facts["reference_s"] = time.perf_counter() - t_ref
    print(f"after the window: trace read {t_ref - t_close:.2f} s, "
          f"reference {facts['reference_s']:.2f} s", file=sys.stderr)
    return {"facts": facts, "numbers": numbers,
            "attempted": st["steps"], "failed": 0,
            "end_to_end": {"setup_s": facts["setup_s"],
                           "train_img_per_s": items / window_s}}
