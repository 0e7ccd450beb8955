"""The routed experts' grouped products' share of their roofline: the least
time the chip could take for the rows the router really sent to the held
experts in the window (the program's own counter, summed by the driver into
``facts``: ``expert_rows`` and ``expert_layer_steps``), over the summed device
time of the op events whose module path matches. It reads the same work
whatever implements the product. params: ``pattern``, ``work`` (a function of
``benchmarks/flops/<family>.py`` taking the shapes, the rows and the
executions). No trace, no counter or nothing matched: nothing returned."""

from benchmarks.harness import spec


def read(run, params):
    rows = run.facts.get("expert_rows")
    if run.trace is None or not rows:
        return None
    seconds, count = run.trace.op_seconds(params["pattern"], run.op_paths)
    if not count or seconds <= 0:
        return None
    work = getattr(spec.module("flops", run.config["family"]), params["work"])(
        run.config["shapes"], rows / run.chips,
        run.facts["expert_layer_steps"])
    least = max(work["flops"] / run.peaks["bf16_flops_per_s"],
                work["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
