"""Device time counted once: on a chip's ``XLA Ops`` line a ``while`` or a
``conditional`` event covers the events of the ops it encloses, so a sum of
durations counts those nanoseconds twice (``harness/trace.py::op_seconds``
does; ``PERF.md`` gave a loss of 125 ms for 73 that way). Here each
nanosecond belongs to the innermost event that covers it, the one that
started last: an enclosing event keeps only what its children leave, its own
loop or branch overhead, and the owned times of a line sum to the union
``Trace.busy_seconds`` gives. No reader itself: ``scope_ms_per_step`` and
``unplaced_pct`` share it."""

from __future__ import annotations

import functools

from benchmarks.harness.trace import instruction_of


def owned_ns(ops: list) -> list:
    """``[(name, owned ns)]`` for one line's ``(name, start_ns, dur_ns)``
    events, in order of their starts. Events that overlap without nesting
    share nothing either: the later start owns the overlap."""
    order = sorted(ops, key=lambda e: (e[1], -e[2]))
    owned = [0.0] * len(order)
    open_, cursor = [], 0.0                  # stack of (index, end_ns)
    for i, (_, start, dur) in enumerate(order):
        # hand the time up to this start to whoever was innermost in it
        while open_:
            top, end = open_[-1]
            if end > start:
                owned[top] += max(start - cursor, 0.0)
                break
            owned[top] += max(end - cursor, 0.0)
            cursor = max(cursor, end)
            open_.pop()
        cursor = max(cursor, start) if open_ else start
        open_.append((i, start + dur))
    while open_:
        top, end = open_.pop()
        owned[top] += max(end - cursor, 0.0)
        cursor = max(cursor, end)
    return [(name, ns) for (name, _, _), ns in zip(order, owned)]


@functools.lru_cache(maxsize=1)
def _owned_of(trace) -> tuple:
    return tuple(tuple(owned_ns(rec["ops"])) for rec in trace.devices.values())


def owned_by_path(run) -> list:
    """``[(op path or "", owned seconds per chip)]`` of every op event of
    the run's trace; the path is the ``op_name`` of the event's instruction
    in the compiled step's text, "" where the compiler left none."""
    chips = max(len(run.trace.devices), 1)
    return [(run.op_paths.get(instruction_of(name), ""), ns / 1e9 / chips)
            for line in _owned_of(run.trace) for name, ns in line]
