"""Median device duration of a program's module events in the trace.
params: ``pattern``, a regular expression on the module event's name."""

import statistics


def read(run, params):
    if run.trace is None:
        return None
    durations = run.trace.module_durations_ms(params["pattern"])
    return statistics.median(durations) if durations else None
