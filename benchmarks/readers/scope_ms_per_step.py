"""Device time a step under one of the program's scopes, in milliseconds,
each nanosecond counted once: the owned time (``readers/owned_time.py``) of
the op events whose path matches, over the steps of the window. Where
``op_ms_per_step`` adds a ``while`` event to the ops of its body, this gives
the body's ops plus the loop's own overhead. params: ``pattern`` (regular
expression on the op path; a scope that is the first under a transformation
reads ``jvp(loss_head)``, not ``/loss_head/``). No trace, or a program
without the scope: nothing returned."""

import re

from benchmarks.readers import owned_time


def read(run, params):
    if run.trace is None or not run.facts.get("steps"):
        return None
    rx = re.compile(params["pattern"])
    matched = [s for path, s in owned_time.owned_by_path(run) if rx.search(path)]
    if not matched:
        return None
    return 1e3 * sum(matched) / run.facts["steps"]
