"""A statistic over the window's batches of the program's own spans, in
milliseconds. The driver clears the span ring when the window opens and
disables it once the window has closed, so the ring the program keeps as
``obs.spans.last()`` is the window's timeline. The named spans are summed per
``batch`` (the identifier the feed's worker gives each batch); a batch counts
only if it has every one of the names, so one half-recorded at either edge of
the window does not. params: ``names`` (span names), ``stat`` (a function of
``statistics``, ``median`` say). A program without ``last()``, a ring that was
never on, or no complete batch: nothing returned."""

import statistics
from collections import defaultdict


def batch_sums_ms(events, names) -> list:
    """Per batch that carries all of ``names``, the summed duration in ms."""
    names = set(names)
    per_batch = defaultdict(dict)
    for e in events:
        batch = (e.get("args") or {}).get("batch")
        if e.get("ph") == "X" and e.get("name") in names and batch is not None:
            seen = per_batch[batch]
            seen[e["name"]] = seen.get(e["name"], 0.0) + e["dur"] / 1e3
    return [sum(seen.values()) for _, seen in sorted(per_batch.items())
            if seen.keys() == names]


def read(run, params):
    try:
        from deeplearning_tpu.obs import spans
    except ImportError:
        return None
    last = getattr(spans, "last", None)
    ring = last() if last is not None else None
    if ring is None:
        return None
    sums = batch_sums_ms(ring.events(), params["names"])
    return float(getattr(statistics, params["stat"])(sums)) if sums else None
