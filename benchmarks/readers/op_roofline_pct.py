"""A kernel's share of its roofline: the least time the chip could take for
the work (the larger of operations over peak FLOP/s and bytes over peak
bytes/s, both from the shapes by a function in ``benchmarks/flops``), over
the summed device time of the op events whose module path matches. params:
``pattern`` (regular expression on the op path), ``work`` (function name).
Nothing matched, or no trace: nothing returned."""

from benchmarks.harness import spec


def read(run, params):
    if run.trace is None or not run.facts.get("steps"):
        return None
    seconds, count = run.trace.op_seconds(params["pattern"], run.op_paths)
    if not count or seconds <= 0:
        return None
    per_chip_batch = run.facts["batch"] // run.chips
    work = getattr(spec.module("flops", run.config["family"]),
                   params["work"])(run.config["shapes"], per_chip_batch)
    least = max(work["flops"] / run.peaks["bf16_flops_per_s"],
                work["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * run.facts["steps"] / seconds
