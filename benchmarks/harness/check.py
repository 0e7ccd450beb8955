"""The comparison that decides ``correct`` for a training cell: what the timed
path's first steps produced, against the reference's.

Numbers (each with a limit of its own in ``benchmarks/checks/<cell>.json``):

- ``loss_gap``         worst relative gap of a followed step's loss;
- ``grad_norm_gap``    worst relative gap of a followed step's global gradient
                       norm (before the clip);
- ``grad_leaf_gap``    first gradient as Adam's moments received it, worst
                       leaf: | ||g_program|| - ||g_reference|| | over the larger
                       of the reference's norm of that leaf and of its median
                       leaf (some gradients are all but zero);
- ``change_leaf_gap``  the parameters' change over the followed steps, worst
                       leaf by the same measure. Elements whose reference
                       gradient is under a thousandth of the median leaf's
                       root-mean-square gradient are left out of both norms:
                       they move under Adam by round-off alone (a key's bias
                       under softmax for one, which here is a third of the
                       fused ``qkv/bias`` leaf, so the rule goes by element,
                       not by leaf, and never by name);
- ``ema_leaf_gap``     the moving average's change, likewise;
- ``grad_median_leaf_gap``, ``change_median_leaf_gap``, ``ema_median_leaf_gap``
                       the median leaf's gap where the three above take the
                       worst: the worst leaf is one small bias whose gap swings
                       threefold from seed to seed, the median is steady, and
                       it is the median of the change that parts bfloat16 from
                       fp8 (PR 24's readings, in the cells' ``checks`` files);
- ``rows_wrong``       rows of the followed batches that are not rows of the
                       seed's image set, bit for bit, with their labels.
"""

from __future__ import annotations

import math
import statistics

from benchmarks.references.train_ref import masked_leaf_norms

NEGLIGIBLE_GRADIENT = 1e-3


def _leaf_gaps(program: list, reference: list) -> list:
    floor = statistics.median(reference)
    return [abs(p - r) / max(r, floor) for p, r in zip(program, reference)]


def _change_gaps(program_leaves, reference_leaves, grads, floor) -> list:
    ours = [float(x) for x in masked_leaf_norms(list(program_leaves), grads, floor)]
    theirs = [float(x) for x in masked_leaf_norms(list(reference_leaves), grads, floor)]
    return _leaf_gaps(ours, theirs)


def compare(program: dict, reference: dict) -> dict:
    grads = reference["first_grad"]
    leaves = reference["first_grad_leaves"]
    rms = [g / max(x.size, 1) ** 0.5 for g, x in zip(grads, leaves)]
    floor = NEGLIGIBLE_GRADIENT * statistics.median(rms)
    grad = _leaf_gaps(program["first_grad"], grads)
    change = _change_gaps(program["change"], reference["change"], leaves, floor)
    ema = _change_gaps(program["ema_change"], reference["ema_change"], leaves, floor)
    return {
        "loss_gap": max(abs(p - r) / abs(r)
                        for p, r in zip(program["loss"], reference["loss"])),
        "grad_norm_gap": max(abs(p - r) / r for p, r in
                             zip(program["grad_norm"], reference["grad_norm"])),
        "grad_leaf_gap": max(grad),
        "change_leaf_gap": max(change),
        "ema_leaf_gap": max(ema),
        "grad_median_leaf_gap": statistics.median(grad),
        "change_median_leaf_gap": statistics.median(change),
        "ema_median_leaf_gap": statistics.median(ema),
    }


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, [(name, value, limit)]): every limit has to be met by a number
    that is there; a number that is missing fails."""
    rows, ok = [], True
    for name, limit in limits.items():
        value = numbers.get(name)
        rows.append((name, value, limit))
        ok = ok and value is not None and math.isfinite(value) and value <= limit
    return ok, rows
