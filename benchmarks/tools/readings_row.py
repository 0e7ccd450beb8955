#!/usr/bin/env python
"""The readings a token cell's limits are set from, for a cell whose global
batch is ONE row, where ``readings_tokens.py``'s half-batch fault has no row
to leave out. For each seed, in one process: the program against the
reference (the lower readings), then in the program's place the control (the
reference in fp8) and two faults planted in the reference: the second half of
the row's positions left out, the mean taken over the first half, and the
family's own fault (the keyword the harness hands every family as the fifth
argument of ``loss_sums``, false). A state left unchanged reads 1 and needs
no run. Each is judged against the cell's limits as a run is: the program
has to come out correct, the control and the faults not; the last line says
whether all did (exit code 1 if not).

    python benchmarks/tools/readings_row.py <cell> <seed> ...        # on a TPU host
    JAX_PLATFORMS=cpu python benchmarks/tools/readings_row.py --rehearse <bench file> <cell> <seed> ...
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run                        # noqa: E402
from benchmarks.drivers.train_tokens import compare            # noqa: E402
from benchmarks.harness import check                           # noqa: E402
from benchmarks.references import train_ref, train_ref_lm      # noqa: E402


def half_positions(batches: list) -> list:
    """Each batch's rows cut to their first half of positions: (b, n + 1) ->
    (b, n / 2 + 1), so the first n / 2 positions keep their targets."""
    return [rows[:, :(rows.shape[1] - 1) // 2 + 1] for rows in batches]


def planted(batches: list) -> dict:
    """What takes the program's place, as arguments of the reference."""
    return {"control_fp8": {"mode": "fp8"},
            "fault_half_positions": {"batches": half_positions(batches)},
            "fault_family_own": {"bias_in_choice": False}}


def main() -> int:
    args = sys.argv[1:]
    bench_file = None
    if args[:1] == ["--rehearse"]:
        bench_file, args = args[1], args[2:]
    cell, seeds = args[0], [int(s) for s in args[1:]]
    as_expected = True
    for seed in seeds:
        line, run = bench_run.execute(cell, seed, 0.3, False, bench_file=bench_file,
                                      require_tpu=bench_file is None)
        fam = train_ref.family(run.config["family"])
        common = dict(fam_name=run.config["family"], shapes=run.config["shapes"],
                      recipe=run.config["recipe"], batches=run.reference_inputs,
                      rows=run.traffic["reference_rows"])
        rec = {"cell": cell, "seed": seed,
               "program": compare(run.program, run.reference)}
        faults = planted(run.reference_inputs)
        for name, kw in faults.items():
            out = train_ref_lm.follow(params=fam.make_params(
                fam.param_spec(run.config["shapes"]), seed), **{**common, **kw})
            rec[name] = compare(out, run.reference)
            del out
        still = dict(run.reference, change=[0.0 * x for x in
                                            run.reference["change"]])
        rec["fault_state_unchanged"] = compare(still, run.reference)
        rec["reference_s"] = line["facts"]["reference_s"]
        rec["loss"] = run.reference["loss"]
        rec["memory_peak_bytes"] = line["device"]["memory_peak_bytes"]
        print("READING " + json.dumps(rec), flush=True)
        verdict = {"cell": cell, "seed": seed}
        for name in ("program", *faults, "fault_state_unchanged"):
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
