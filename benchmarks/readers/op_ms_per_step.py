"""Device time a step of the op events whose module path matches, in
milliseconds: their summed duration over the steps of the window. params:
``pattern`` (regular expression on the op path). No trace or nothing matched:
nothing returned."""


def read(run, params):
    if run.trace is None or not run.facts.get("steps"):
        return None
    seconds, count = run.trace.op_seconds(params["pattern"], run.op_paths)
    if not count:
        return None
    return 1e3 * seconds / run.facts["steps"]
