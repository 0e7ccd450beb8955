#!/usr/bin/env python
"""The readings a token cell's limits are set from, in one process: for each
seed, the program against the reference (the lower readings), then in the
program's place the control (the reference in fp8) and two faults planted in
the reference: half of the batch left out with the mean taken over the rest,
and the correction bias left out of the routers' choice. A state left
unchanged reads 1 and needs no run. Each is judged against the cell's limits
as a run is: the program has to come out correct, the control and the faults
not; the last line says whether all did (exit code 1 if not).

``--choices`` also counts, on the first seed's first followed batch and the
starting weights, the (token, layer) choices of the program's routers that
differ from the reference's.

    chiprun -- python benchmarks/tools/readings_tokens.py [--choices] <cell> <seed> ...
    JAX_PLATFORMS=cpu python benchmarks/tools/readings_tokens.py --rehearse <bench file> <cell> <seed> ...
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

def planted(rows_in_batch: int) -> dict:
    """What takes the program's place, as arguments of the reference."""
    return {"control_fp8": {"mode": "fp8"},
            "fault_half_batch": {"skip_rows": range(rows_in_batch // 2,
                                                    rows_in_batch)},
            "fault_bias_not_in_choice": {"bias_in_choice": False}}


def choices_that_differ(run, seed: int) -> dict:
    """(token, layer) top-k choices of the program (its own precision) that
    are not the reference's, as sets, on the first followed batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning_tpu.core.registry import MODELS

    shapes, fam = run.config["shapes"], train_ref.family(run.config["family"])
    params = fam.make_params(fam.param_spec(shapes), seed)
    tokens = jnp.asarray(run.reference_inputs[0])
    model = MODELS.build(run.config["registry_name"],
                         num_classes=shapes["vocab_size"], dtype=jnp.bfloat16)

    @jax.jit
    def program(params, tokens):
        _, mutated = model.apply({"params": params}, tokens[:, :-1],
                                 next_tokens=tokens[:, 1:], return_hidden=True,
                                 mutable=["intermediates"])
        return mutated["intermediates"]
    mine = {"/".join(k.key for k in path if hasattr(k, "key")
                     and k.key not in ("moe", "choice")): v
            for path, v in jax.tree_util.tree_leaves_with_path(
                program(params, tokens))}
    reference = jax.jit(lambda p, t: fam.hidden_states(
        p, t[:, :-1], t[:, 1:], shapes, "f32")[1])
    out = {}
    for row in range(tokens.shape[0]):
        theirs = reference(params, tokens[row:row + 1])
        n = tokens.shape[1] - 1
        for layer, idx in theirs.items():
            a = np.sort(np.asarray(mine[layer])[row * n:(row + 1) * n], axis=-1)
            b = np.sort(np.asarray(idx), axis=-1)
            out[layer] = out.get(layer, 0) + int(np.sum(np.any(a != b, axis=-1)))
    return {"differ_by_layer": out, "differ": sum(out.values()),
            "of": tokens.shape[0] * (tokens.shape[1] - 1) * len(out)}


def main() -> int:
    args = sys.argv[1:]
    bench_file, choices = None, False
    if args[:1] == ["--rehearse"]:
        bench_file, args = args[1], args[2:]
    if args[:1] == ["--choices"]:
        choices, args = True, args[1:]
    cell, seeds = args[0], [int(s) for s in args[1:]]
    as_expected = True
    for seed in seeds:
        line, run = bench_run.execute(cell, seed, 0.3, False, bench_file=bench_file,
                                      require_tpu=bench_file is None)
        batches = run.reference_inputs
        fam = train_ref.family(run.config["family"])
        common = dict(fam_name=run.config["family"], shapes=run.config["shapes"],
                      recipe=run.config["recipe"], batches=batches,
                      rows=run.traffic["reference_rows"])
        rec = {"cell": cell, "seed": seed,
               "program": compare(run.program, run.reference)}
        faults = planted(len(batches[0]))
        for name, kw in faults.items():
            out = train_ref_lm.follow(params=fam.make_params(
                fam.param_spec(run.config["shapes"]), seed), **common, **kw)
            rec[name] = compare(out, run.reference)
            del out
        still = dict(run.reference, change=[0.0 * x for x in
                                            run.reference["change"]])
        rec["fault_state_unchanged"] = compare(still, run.reference)
        rec["reference_s"] = line["facts"]["reference_s"]
        rec["loss"] = run.reference["loss"]
        rec["memory_peak_bytes"] = line["device"]["memory_peak_bytes"]
        if choices:
            rec["choices"] = choices_that_differ(run, seed)
            choices = False
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
