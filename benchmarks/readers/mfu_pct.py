"""The whole step's share of the chips' peak: operations the forward and
backward passes require (a function of the configuration's shapes, kept in
``benchmarks/flops/<family>.py``) times the items completed in the window,
over the window and the chips' peak. params: ``work``, the function's name."""

from benchmarks.harness import spec


def read(run, params):
    window = run.facts.get("window_s")
    if not window or not run.facts.get("items"):
        return None
    per_item = getattr(spec.module("flops", run.config["family"]),
                       params["work"])(run.config["shapes"])
    achieved = per_item * run.facts["items"] / window
    return 100.0 * achieved / (run.chips * run.peaks["bf16_flops_per_s"])
