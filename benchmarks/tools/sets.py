#!/usr/bin/env python
"""Two sets of runs of one cell, each run a new process as the driver makes
them, with the same seeds in both sets; then each end-to-end metric's spread
(interquartile distance over the median, by ``statistics.quantiles(n=4)``) in
each set, the bound that five times the wider spread would give, and how far
the second set's median lies from the first's. The very first run compiles and
its set-up is shown apart. This process never touches JAX.

    chiprun --timeout 2400 -- python benchmarks/tools/sets.py <cell> <seconds> <seed> [<seed> ...]
    ... sets.py --trace <cell> <seconds> <seed> ...   # one traced run per seed, metrics only
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def one(cell: str, seconds: str, seed: int, trace: int) -> dict:
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed",
         str(seed), "--seconds", seconds, "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True)
    if out.returncode != 0 or not out.stdout.strip():
        print(out.stderr[-3000:], flush=True)
        raise SystemExit(f"run of {cell} seed {seed} failed: rc {out.returncode}")
    line = json.loads(out.stdout.strip().splitlines()[-1])
    for text in out.stderr.splitlines():
        if text.startswith(("setup:", "window:", "reference:", "after the window:")):
            print("    " + text, flush=True)
    print("RUN " + json.dumps({"cell": cell, "seed": seed, "trace": trace, **line}),
          flush=True)
    return line


def spread(values: list) -> float:
    if len(values) < 2:
        return float("nan")     # two seeds, the compiling run taken out
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    args = sys.argv[1:]
    trace = args[:1] == ["--trace"]
    if trace:
        args = args[1:]
    cell, seconds, seeds = args[0], args[1], [int(s) for s in args[2:]]
    if trace:
        for seed in seeds:
            one(cell, seconds, seed, 1)
        return 0
    sets = [[one(cell, seconds, seed, 0) for seed in seeds] for _ in range(2)]
    wrong = [r for s in sets for r in s if not r["correct"]]
    names = sets[0][0]["metrics"].keys()
    for name in names:
        values = [[r["metrics"][name]["value"] for r in s] for s in sets]
        if name == "setup_s":
            print(f"SET {cell} setup_s first (compiling) run {values[0][0]:.2f}")
            values[0] = values[0][1:]
        med = [statistics.median(v) for v in values]
        spr = [spread(v) for v in values]
        print("SET " + json.dumps({
            "cell": cell, "metric": name, "medians": med, "spreads": spr,
            "bound_at_5x": 5 * max(spr), "second_over_first": med[1] / med[0] - 1,
            "values": values}), flush=True)
    print(f"SET {cell} correct in {sum(len(s) for s in sets) - len(wrong)} "
          f"of {sum(len(s) for s in sets)} runs")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
