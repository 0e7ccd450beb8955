#!/usr/bin/env python
"""The readings a training cell's limits are set from, in one process (set-up
is long): for each seed, the program against the reference (the lower
readings), the control (the reference in fp8 in the program's place) and the
fault "half of the batch left out, the mean taken over the rest" planted in the
reference in the program's place (the upper readings). A step that returns its
state unchanged reads 1 by the measure and needs no run: it is the reference
with its change set to nought. Each is then judged against the cell's limits
as a run is (``check.judge``): the program has to come out correct, the control
and the faults not, and the last line says whether all did (exit code 1 if not).

    chiprun -- python benchmarks/tools/readings.py <cell> <seed> [<seed> ...]
    JAX_PLATFORMS=cpu python benchmarks/tools/readings.py --rehearse <cell> <seed> ...
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run                    # noqa: E402
from benchmarks.harness import check                       # noqa: E402
from benchmarks.references import train_ref                # noqa: E402


def in_programs_place(out: dict) -> dict:
    return {k: out[k] for k in ("loss", "grad_norm", "first_grad", "change",
                                "ema_change")}


def main() -> int:
    args = sys.argv[1:]
    rehearse = args[:1] == ["--rehearse"]
    if rehearse:
        args = args[1:]
    cell, seeds = args[0], [int(s) for s in args[1:]]
    bench_file = os.path.join(ROOT, "benchmarks", "tests", "bench_rehearse.json") \
        if rehearse else None
    as_expected = True
    for seed in seeds:
        line, run = bench_run.execute(cell, seed, 0.3, False, bench_file=bench_file,
                                      require_tpu=not rehearse)
        batches, keeps = run.reference_inputs
        common = dict(fam_name=run.config["family"], shapes=run.config["shapes"],
                      recipe=run.config["recipe"], batches=batches, keeps=keeps,
                      rows=run.traffic["reference_rows"])

        def params():
            fam = train_ref.family(run.config["family"])
            return train_ref.make_params(fam.param_spec(run.config["shapes"]), seed)
        rec = {"cell": cell, "seed": seed,
               "program": check.compare(run.program, run.reference)}
        for name, kw in (("control_fp8", {"mode": "fp8"}),
                         ("fault_half_batch", {"skip_rows": range(
                             len(batches[0][1]) // 2, len(batches[0][1]))})):
            out = train_ref.follow(params=params(), **common, **kw)
            rec[name] = check.compare(in_programs_place(out), run.reference)
        still = in_programs_place(run.reference)
        for key in ("change", "ema_change"):
            still[key] = [0.0 * x for x in still[key]]
        rec["fault_state_unchanged"] = check.compare(still, run.reference)
        rec["reference_s"] = line["facts"]["reference_s"]
        rec["loss"] = run.reference["loss"]
        print("READING " + json.dumps(rec), flush=True)
        verdict = {"cell": cell, "seed": seed}
        for name in ("program", "control_fp8", "fault_half_batch",
                     "fault_state_unchanged"):
            ok, rows = check.judge({**rec[name], "rows_wrong": 0.0},
                                   run.checks["limits"])
            verdict[name] = {"correct": ok, "over_limit": [
                n for n, v, lim in rows if not v <= lim]}
            as_expected = as_expected and ok == (name == "program")
        print("VERDICT " + json.dumps(verdict), flush=True)
        run.reference = run.reference_inputs = run.program = None
    print(f"VERDICTS as expected (program correct, control and faults not "
          f"correct) on every seed: {as_expected}", flush=True)
    return 0 if as_expected else 1


if __name__ == "__main__":
    raise SystemExit(main())
