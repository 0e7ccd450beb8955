#!/usr/bin/env python
"""Drive a whole run of the harness on the CPU at a rehearsal size (the look
for a chip skipped): JAX_PLATFORMS=cpu python benchmarks/tests/rehearse.py
<workload> [seed] [seconds] [trace] [directory to keep the trace in]"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
from benchmarks import run as bench_run  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def main():
    workload = sys.argv[1]
    seed = int(sys.argv[2]) if len(sys.argv) > 2 else 3000000019
    seconds = float(sys.argv[3]) if len(sys.argv) > 3 else 3.0
    trace = bool(int(sys.argv[4])) if len(sys.argv) > 4 else False
    keep = sys.argv[5] if len(sys.argv) > 5 else None
    line, run = bench_run.execute(
        workload, seed, seconds, trace, require_tpu=False, keep_dir=keep,
        bench_file=os.path.join(HERE, "bench_rehearse.json"))
    print(json.dumps(line))


if __name__ == "__main__":
    main()
