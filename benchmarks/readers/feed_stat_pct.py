"""A feed counter accumulated inside the window, as a share of the window.
params: ``stat``, a key of the driver's ``feed`` facts (seconds)."""


def read(run, params):
    value = run.facts.get("feed", {}).get(params["stat"])
    if value is None or not run.facts.get("window_s"):
        return None
    return 100.0 * value / run.facts["window_s"]
