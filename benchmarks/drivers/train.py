"""The train driver: ``tools/train.py::build_trainer`` -> ``Trainer.train``,
the path a user's run takes, with the feed running.

Set-up builds one trainer, hands it the benchmark's weights, and drives it
through ``Trainer.train`` itself: the first ``warmup_steps`` steps are waited
for one by one (the first ``followed_steps`` of them are what the reference
follows afterwards), then the window opens. Inside the window no step is waited
for; a callback closes it by raising, at the step boundary from which the steps
still in flight carry the device to ``--seconds``; the driver catches that and
waits for the state. Images of all steps dispatched in the window, over the
time until that state is ready, is the rate.
"""

from __future__ import annotations

import collections
import gc
import os
import shutil
import sys
import time

import numpy as np

from benchmarks.harness import check, data, spec
from benchmarks.harness.trace import Trace, find_xplane, op_paths
from benchmarks.references import droppath, train_ref


class _CloseWindow(Exception):
    """Raised by the driver's callback to leave ``Trainer.train``."""


def _moments(opt_state):
    """Adam's first moments inside an optax chain's state."""
    import jax
    found = [n for n in jax.tree.leaves(opt_state, is_leaf=lambda x: hasattr(x, "mu"))
             if hasattr(n, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"{len(found)} Adam states in the optimizer state")
    return found[0].mu


def _hook(obj, name: str, why: str):
    """A private member of the program that the driver has to reach. Where a
    later PR renames it the run ends here, not with a metric silently gone."""
    try:
        return getattr(obj, name)
    except AttributeError:
        raise spec.SpecError(f"{type(obj).__name__} has no {name!r} any more; "
                             f"the train driver needs it {why}") from None


def _memory_peak(devices, on_chip: bool) -> int:
    """Peak bytes on the fullest chip. On this client ``peak_bytes_in_use``
    leaves a running program's temporaries out; they are allocated in a
    region of their own that ``peak_bytes_reserved`` counts (PR 24, step 0:
    7.38 GB reserved beside 7.47 GB of ``temp_size_in_bytes``), so the peak
    is the sum of the two. The two peaks need not fall at the same moment,
    so the sum is an upper bound; the driver keeps its own copies of the
    weights off the device so that the resident peak is the program's."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if not on_chip and not stats:
            return 0          # a CPU rehearsal: the client keeps no statistics
        missing = {"peak_bytes_in_use", "peak_bytes_reserved"} - stats.keys()
        if missing:
            raise spec.SpecError(f"memory_stats() of {d} lacks {sorted(missing)}: "
                                 "the memory figure would leave the step's "
                                 "temporaries out")
        peaks.append(int(stats["peak_bytes_in_use"])
                     + int(stats["peak_bytes_reserved"]))
    return max(peaks)


def run(run) -> dict:
    import jax
    import jax.numpy as jnp

    sys.path[:0] = [p for p in (spec.ROOT, os.path.join(spec.ROOT, "tools"))
                    if p not in sys.path]
    try:
        import train as train_cli
        from deeplearning_tpu.core.config import config_cli
        from deeplearning_tpu.obs import spans
        from deeplearning_tpu.obs.xla import compile_events
    except ImportError as exc:
        raise spec.SpecError(f"the program is not in this checkout: {exc}")

    marks = [("start", run.t_start), ("imports", time.perf_counter())]

    def mark(name):
        marks.append((name, time.perf_counter()))

    traffic, config = run.traffic, run.config
    shapes, recipe = config["shapes"], config["recipe"]
    fam = train_ref.family(config["family"])
    gb, warm, followed = (traffic["global_batch"], traffic["warmup_steps"],
                          traffic["followed_steps"])
    if not 0 < followed <= warm:
        raise spec.SpecError("followed_steps must lie within warmup_steps")

    # ---- inputs from the seed
    images, labels = data.image_set(run.seed, traffic["dataset_images"],
                                    shapes["image_size"], shapes["num_classes"])
    npz = data.write_npz(os.path.join(run.cache_dir, "data"),
                         f"images_{traffic['dataset_images']}x{shapes['image_size']}",
                         run.seed, images, labels)
    mark("data")
    argv = [a.replace("{root}", spec.ROOT) for a in config["argv"]] \
        + list(traffic["argv"]) + [f"data.npz={npz}",
                                   f"data.global_batch={gb}",
                                   f"train.seed={run.seed}"]
    trainer = train_cli.build_trainer(config_cli(train_cli.Config(), argv),
                                      devices=run.devices)
    trainer.epochs = trainer.eval_every = 10 ** 9   # no eval inside the window
    # The small set is cycled inside one Trainer epoch (the loader's own
    # ``infinite`` mode, reshuffled every pass), standing for an ImageNet
    # epoch's 10,009 steps: with 32-step epochs the epoch end's drain and
    # feed restart (0.14-0.23 s of idle device each, PR 24) would weigh
    # 300 times what they weigh in a real run and set the spread.
    feed = trainer.train_loader
    loader = _hook(feed, "loader", "to cycle the image set without epoch ends")
    _hook(loader, "infinite", "to cycle the image set without epoch ends")
    loader.infinite = True
    # drops the finite pipeline that precompile() started
    _hook(feed, "reseed", "to restart the feed on the endless loader")(0)
    aot_text = _hook(_hook(trainer, "_aot_step", "for the compiled step's text"),
                     "as_text", "for the op paths of the traced run")
    _hook(trainer, "_batches", "to stop the feed's threads after the window")
    mark("build_trainer")

    # ---- the benchmark's weights, in the program's tree. The program's own
    # draw is dropped first and the driver's copy of the start is kept on the
    # host, so that the device never holds more than the program's state and
    # ``memory_peak_bytes`` counts none of the harness.
    theirs = trainer.state.params
    shardings = jax.tree.map(lambda x: x.sharding, theirs)
    shapes_theirs = jax.tree.map(lambda x: x.shape, theirs)
    paths_theirs = train_ref.leaf_paths(theirs)
    del theirs
    trainer.state = trainer.state.replace(params=None, ema_params=None)
    start = train_ref.make_params(fam.param_spec(shapes), run.seed)
    if shapes_theirs != jax.tree.map(lambda x: x.shape, start):
        raise spec.SpecError(
            "the configuration's file and the program disagree on the parameters: "
            + str(sorted(set(paths_theirs) ^ set(train_ref.leaf_paths(start)))[:8]))
    start_host = jax.tree.leaves(jax.device_get(start))
    trainer.state = trainer.state.replace(
        params=jax.device_put(start, shardings),
        ema_params=jax.device_put(jax.tree.map(jnp.copy, start), shardings))
    del start
    if run.sabotage is not None:
        run.sabotage(trainer)
    mark("weights")

    st = {"n": 0, "steps": 0, "rows": [], "loss": [], "grad_norm": [],
          "feed_wait": 0.0, "open": None, "start": start_host, "before": [], "after": [],
          "pending": collections.deque(), "done": 0}
    trace_dir = os.path.join(run.cache_dir, "trace")

    def before(tr, batch):
        if st["n"] < followed:
            st["rows"].append((np.asarray(batch["image"]), np.asarray(batch["label"])))
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
            # The host runs ahead of the device by up to some thirty steps.
            # Look, without waiting, at which steps are done, and close once
            # the steps in flight will carry the device to --seconds.
            st["pending"].append(metrics["loss"])
            while st["pending"] and st["pending"][0].is_ready():
                st["pending"].popleft()
                st["done"] += 1
            elapsed = st["after"][-1] - st["open"]
            ahead = (st["steps"] - st["done"]) * elapsed / st["done"] \
                if st["done"] >= 8 else 0.0
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
            # taken on the host: the comparison leaves single elements out,
            # and a difference made on the device would count in its peak
            for key, tree in (("change", tr.state.params),
                              ("ema_change", tr.state.ema_params)):
                st[key] = [np.asarray(a, np.float32) - b for a, b in
                           zip(jax.tree.leaves(jax.device_get(tree)), st["start"])]
            st["start"] = None
        if n == warm:
            if run.traced:
                # device events only: with host events on, the feed thread's
                # layout transposes write some 200,000 events a batch and the
                # feed starves (PR 24: feed wait 49 % traced, under 1 % not).
                # The program's spans come from its own ring instead.
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
    epochs = trainer.epoch
    # the host's view of the window: where it stood between two steps for over
    # half a second (an epoch boundary's drain, a starved feed), and how long the
    # device took to finish what was in flight when the window closed
    stalls = [(i, round(b - a, 3)) for i, (a, b) in
              enumerate(zip(st["after"], st["before"][1:]), start=1) if b - a > 0.5]
    print(f"window: {st['steps']} steps of {gb} images in {window_s:.3f} s, "
          f"{epochs} epoch boundaries; host stalls after step {stalls}; last "
          f"dispatch at {st['after'][-1] - st['open']:.3f} s, drained "
          f"{t_close - st['after'][-1]:.3f} s later", file=sys.stderr, flush=True)

    facts = {
        "setup_s": st["open"] - run.t_start,
        "window_s": window_s, "steps": st["steps"], "batch": gb,
        "items": st["steps"] * gb, "epoch_boundaries": epochs,
        "feed": {"data_wait_s": st["feed_wait"]},
        "compile_events": [dict(e) for e in compile_events()],
        "memory_peak_bytes": _memory_peak(run.devices, run.peaks is not None),
    }
    hlo_text = aot_text() if run.traced else ""

    # ---- free the program's state, then the reference
    _hook(trainer._batches, "close", "to stop the feed's threads")()
    trainer.callbacks = None
    del trainer
    gc.collect()

    if run.traced:
        xplane = find_xplane(trace_dir)
        run.trace = Trace.from_file(xplane)
        run.trace.place_host_spans(
            host_events, traffic.get("host_spans", ()), anchor="dispatch")
        run.op_paths = op_paths(hlo_text)
        if not run.op_paths:
            raise spec.SpecError("the compiled step's text names no op path: the "
                                 "roofline metrics and the breakdown would go "
                                 "silent while the kernels still run")
        if run.keep_dir:
            os.makedirs(run.keep_dir, exist_ok=True)
            shutil.copy(xplane, os.path.join(run.keep_dir, "trace.xplane.pb"))
            with open(os.path.join(run.keep_dir, "step.hlo.txt"), "w") as f:
                f.write(hlo_text)
        shutil.rmtree(trace_dir, ignore_errors=True)

    t_ref = time.perf_counter()
    index = data.row_index(images)
    matched = [data.match_rows(x, y, images, labels, index) for x, y in st["rows"]]
    rows_wrong = sum(m["wrong"] for m in matched)
    t_rows = time.perf_counter() - t_ref
    program = {k: st[k] for k in ("loss", "grad_norm", "first_grad", "change",
                                  "ema_change")}
    if rows_wrong:
        numbers = {"rows_wrong": float(rows_wrong)}
    else:
        batches = [(m["images"], m["labels"]) for m in matched]
        sites = fam.droppath_sites(shapes)
        keeps = [droppath.keep_factors(run.seed, t, sites, gb)
                 for t in range(followed)]
        again = train_ref.make_params(fam.param_spec(shapes), run.seed)
        jax.block_until_ready(again)
        t_inputs = time.perf_counter()
        ref = train_ref.follow(
            fam_name=config["family"], shapes=shapes, recipe=recipe,
            params=again, batches=batches, keeps=keeps,
            rows=traffic["reference_rows"])
        t_follow = time.perf_counter()
        numbers = check.compare(program, ref)
        numbers["rows_wrong"] = 0.0
        run.reference, run.reference_inputs = ref, (batches, keeps)
        print(f"reference: inputs {t_inputs - t_ref - t_rows:.2f} s, steps "
              f"{t_follow - t_inputs:.2f} s, comparison "
              f"{time.perf_counter() - t_follow:.2f} s", file=sys.stderr)
    run.program = program
    facts["reference_s"] = time.perf_counter() - t_ref
    print(f"after the window: trace read {t_ref - t_close:.2f} s, "
          f"reference {facts['reference_s']:.2f} s (rows matched in {t_rows:.2f})",
          file=sys.stderr)
    return {"facts": facts, "numbers": numbers,
            "attempted": st["steps"], "failed": 0,
            "end_to_end": {"setup_s": facts["setup_s"],
                           "train_img_per_s": facts["items"] / window_s}}
