"""The share of the device's busy time that the program's own names do not
place, in per cent of all owned time (``readers/owned_time.py``: each
nanosecond once, so the whole is the busy union). An op event is unplaced
where the compiled step gives its instruction no op path, or where the path
names no module and no scope: what is left of it is the primitive alone once
the components that are a transformation's own are taken away. JAX writes a
transformation round the first scope under it (``transpose(jvp(loss_head))``),
so those are opened first; then a component that matches ``wrappers`` whole
goes (``jit(step_fn)``, ``checkpoint``, ``while``, ``body``, the root
module's class name, which covers the whole pass and places nothing). Of a
path joined from several with ``;`` one placed part places the event. params:
``wrappers`` (regular expression, matched against a whole component),
``named_by`` (regular expression on the op path: the scopes by which a
program names its step). No trace, or a program whose step carries none of
those names (one from before it had them): nothing returned."""

import re

from benchmarks.readers import owned_time

_TRANSFORM = re.compile(r"^(?:jvp|transpose|vmap|remat|custom_jvp|custom_vjp)"
                        r"\((.*)\)$")


def placed(path: str, wrappers) -> bool:
    for part in path.split(";"):
        scopes = part.split("/")[:-1]        # the last is the primitive
        for name in scopes:
            while (m := _TRANSFORM.match(name)):
                name = m.group(1)
            if not wrappers.fullmatch(name):
                return True
    return False


def read(run, params):
    if run.trace is None:
        return None
    wrappers, named_by = (re.compile(params[k]) for k in ("wrappers", "named_by"))
    owned = owned_time.owned_by_path(run)
    if not any(named_by.search(path) for path, _ in owned):
        return None
    total = unplaced = 0.0
    for path, seconds in owned:
        total += seconds
        if not path or not placed(path, wrappers):
            unplaced += seconds
    return 100.0 * unplaced / total if total > 0 else None
