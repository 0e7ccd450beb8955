#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json`` and what belongs to it under
``benchmarks/`` by name (see ``benchmarks/README.md``), runs the cell's driver
on the machine it is started on, and prints one JSON object as the last line of
its standard output. Without a TPU, with fewer chips than the cell asks for, or
with a chip whose peaks it does not know, it prints no result and exits 2.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
CACHE_DIR = os.path.join(ROOT, ".bench_cache")
# The compile cache: a fixed directory inside the checkout, whatever the machine
# offers, so that two checkouts share nothing; not capped (the chip machine's
# own is, at 192 MiB, and evicts). JAX reads both when it is first imported, so
# they are set before any import of the benchmark's that reaches it, and the
# program's ``enable_compile_cache`` takes the directory it finds here.
JAX_CACHE = {"dir": os.path.join(CACHE_DIR, "jax"), "max_size": 16 * 2 ** 30}
os.environ["JAX_COMPILATION_CACHE_DIR"] = JAX_CACHE["dir"]
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = str(JAX_CACHE["max_size"])


class Run:
    """One run of one cell: what the driver and the readers are handed."""

    def __init__(self, loaded: dict, seed: int, seconds: float, trace: bool,
                 devices, peaks, keep_dir=None, sabotage=None):
        self.config, self.traffic = loaded["config"], loaded["traffic"]
        self.cell, self.checks = loaded["cell"], loaded["checks"]
        self.chips = loaded["cell"]["chips"]
        self.seed, self.seconds, self.traced = seed, seconds, trace
        self.devices, self.peaks = devices, peaks
        self.cache_dir, self.keep_dir, self.sabotage = CACHE_DIR, keep_dir, sabotage
        self.t_start = T_START
        self.facts, self.trace, self.op_paths = {}, None, {}   # trace: a Trace
        self.program = self.reference = self.reference_inputs = None


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            bench_file=None, require_tpu=True, keep_dir=None, sabotage=None):
    """Run a cell; returns (result line as a dict, the Run). ``require_tpu``
    false is for the self-checks under ``benchmarks/tests``: such a run's
    device says ``cpu`` and it carries no device metric."""
    from benchmarks.harness import check, peaks, spec

    loaded = spec.load_cell(workload, bench_file)
    import jax
    # for a caller that had imported JAX before this module (the self-checks)
    jax.config.update("jax_compilation_cache_dir", JAX_CACHE["dir"])
    jax.config.update("jax_compilation_cache_max_size", JAX_CACHE["max_size"])

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    chips = loaded["cell"]["chips"]
    if require_tpu:
        if platform != "tpu":
            raise NoChip(f"JAX found platform {platform!r}, no TPU")
        if len(devices) < chips:
            raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devices)}")
        chip_peaks = peaks.peaks_of(kind)
    else:
        chip_peaks = None
    devices = devices[:chips]
    run = Run(loaded, seed, seconds, trace, devices, chip_peaks, keep_dir, sabotage)
    out = spec.module("drivers", loaded["traffic"]["driver"]).run(run)
    run.facts = out["facts"]

    if trace:
        metrics = {}
        if chip_peaks is not None:
            for m in loaded["per_layer"]:
                value = spec.module("readers", m["reader"]).read(run, m["params"])
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        units = {m["name"]: m["unit"] for m in loaded["end_to_end"]}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in out["end_to_end"].items() if k in units}
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": run.facts["memory_peak_bytes"]}
    line = {"correct": False, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    if trace and run.trace is not None and chip_peaks is not None:
        device["busy_s"] = run.trace.busy_seconds()
        device["window_s"] = run.trace.window_seconds()
        line["breakdown"] = {"device_ops": run.trace.top_ops(run.op_paths),
                             "idle_gaps": run.trace.idle_gaps()}
    line["facts"] = {k: run.facts[k] for k in
                     ("steps", "window_s", "epoch_boundaries", "reference_s")}
    correct, rows = check.judge(out["numbers"], loaded["checks"]["limits"])
    line["correct"] = bool(correct and out["failed"] == 0)
    line["compared"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in rows}
    return line, run


class NoChip(Exception):
    pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchmarks.harness import peaks, spec
    try:
        line, _ = execute(args.workload, args.seed, args.seconds, bool(args.trace))
    except (NoChip, peaks.UnknownDevice, spec.SpecError, FileNotFoundError) as exc:
        print(f"benchmarks/run.py: {exc}", file=sys.stderr)
        return 2
    for name, row in line["compared"].items():
        print(f"compared {name}: {row['value']} (limit {row['limit']})",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
