"""Structural jaxpr auditor: reusable invariant checks over traced fns.

``tests/test_blocked_nms.py`` proved the no-N×N-memory claim by walking
the jaxpr inline; that walk is the general tool for every structural
invariant this repo cares about — peak intermediate size (does the
postprocess really stay O(N·B)?), transfer counts (does the train step
really dispatch zero ``device_put``s?), and collective counts (what does
a sharded step actually all-reduce? — the accounting PAPERS.md
"Automatic Cross-Replica Sharding of Weight Update" and "EQuARX"
optimizations start from). This module is that walk, shared: usable from
any test and from ``tools/check.py --jaxpr`` over the registered
step/postprocess functions.

Everything reasons over ``jax.make_jaxpr`` output — tracing only, no
compile, no device execution — so audits are cheap even on the 1-core
build box.
"""

from __future__ import annotations

import math
import re
from functools import partial
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax

__all__ = [
    "iter_eqns", "iter_avals", "peak_intermediate",
    "assert_peak_intermediate_below", "count_primitive",
    "count_transfers", "count_collectives", "collective_bytes",
    "hlo_collectives", "count_hlo_collectives", "hlo_collective_bytes",
    "Audit", "builtin_audits", "run_audits",
]

# primitives that move bytes between host and device (or between
# devices) when they appear inside a traced computation
TRANSFER_PRIMITIVES = ("device_put", "copy")

# cross-replica communication primitives (jax.lax collectives + the
# names GSPMD lowers shard_map bodies to)
COLLECTIVE_PRIMITIVES = (
    "psum", "pmax", "pmin", "pmean", "all_gather", "all_to_all",
    "ppermute", "pshuffle", "reduce_scatter", "psum_scatter",
    "pbroadcast", "allreduce",
)


def _as_jaxpr(obj):
    """Accept Jaxpr, ClosedJaxpr, or anything with a ``.jaxpr``."""
    if hasattr(obj, "eqns"):
        return obj
    if hasattr(obj, "jaxpr"):
        return obj.jaxpr
    raise TypeError(f"not a jaxpr: {type(obj)!r}")


def iter_eqns(jaxpr) -> Iterable[Any]:
    """Every equation in ``jaxpr`` and all nested sub-jaxprs (pjit
    bodies, scan/while/cond branches, custom_* calls)."""
    jaxpr = _as_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            if hasattr(p, "eqns") or hasattr(p, "jaxpr"):
                yield from iter_eqns(p)
            elif isinstance(p, (tuple, list)):
                for q in p:
                    if hasattr(q, "eqns") or hasattr(q, "jaxpr"):
                        yield from iter_eqns(q)


def iter_avals(jaxpr) -> Iterable[Any]:
    """Abstract values of every equation OUTPUT, nested jaxprs included
    — the exact set the original inline walk in test_blocked_nms.py
    measured (inputs/consts excluded), kept identical so ported bounds
    stay bitwise the same."""
    for eqn in iter_eqns(jaxpr):
        for v in eqn.outvars:
            yield v.aval


def _trace(fn: Callable, *args, **kwargs):
    return jax.make_jaxpr(fn)(*args, **kwargs)


def peak_intermediate(fn: Callable, *args, **kwargs) -> int:
    """Largest intermediate (in ELEMENTS, not bytes) any equation in the
    traced ``fn(*args)`` produces; 0 when there are no shaped outputs.
    Scalars count as 1 element (``prod(()) == 1``)."""
    closed = _trace(fn, *args, **kwargs)
    return max((int(math.prod(a.shape)) for a in iter_avals(closed.jaxpr)
                if getattr(a, "shape", None) is not None), default=0)


def assert_peak_intermediate_below(fn: Callable, args: Tuple,
                                   max_elements: int,
                                   msg: str = "") -> int:
    """Assert the traced ``fn(*args)`` never materializes an
    intermediate above ``max_elements`` elements. Returns the measured
    peak so callers can report/log it."""
    peak = peak_intermediate(fn, *args)
    assert peak <= max_elements, (
        f"peak intermediate {peak} elements exceeds budget "
        f"{max_elements}" + (f" ({msg})" if msg else ""))
    return peak


def count_primitive(fn: Callable, name, *args, **kwargs) -> int:
    """Occurrences of primitive(s) ``name`` (a str or tuple of strs) in
    the traced ``fn(*args)``, nested jaxprs included."""
    names = (name,) if isinstance(name, str) else tuple(name)
    closed = _trace(fn, *args, **kwargs)
    return sum(1 for eqn in iter_eqns(closed.jaxpr)
               if eqn.primitive.name in names)


def count_transfers(fn: Callable, *args, **kwargs) -> int:
    """Host/device transfer primitives inside the traced computation.
    The sync-free hot-loop contract says this is 0 for the train step:
    batches arrive placed (DevicePrefetcher) and metrics leave lazily
    (DeferredMetrics), so nothing inside the step moves bytes itself."""
    return count_primitive(fn, TRANSFER_PRIMITIVES, *args, **kwargs)


def count_collectives(fn: Callable, *args,
                      axis_env: Optional[List[Tuple[str, int]]] = None,
                      **kwargs) -> Dict[str, int]:
    """Per-primitive counts of cross-replica collectives in the traced
    ``fn(*args)`` — ``{"psum": 2, "all_gather": 1}``-shaped; empty when
    the computation is collective-free. ``axis_env`` names mapped axes
    for functions that psum over an axis outside pmap/shard_map (same
    contract as ``jax.make_jaxpr``'s)."""
    mk = jax.make_jaxpr(fn, axis_env=axis_env) if axis_env else \
        jax.make_jaxpr(fn)
    closed = mk(*args, **kwargs)
    out: Dict[str, int] = {}
    for eqn in iter_eqns(closed.jaxpr):
        nm = eqn.primitive.name
        if nm in COLLECTIVE_PRIMITIVES:
            out[nm] = out.get(nm, 0) + 1
    return out


def collective_bytes(fn: Callable, *args,
                     axis_env: Optional[List[Tuple[str, int]]] = None,
                     **kwargs) -> Dict[str, int]:
    """Per-primitive OPERAND bytes of cross-replica collectives in the
    traced ``fn(*args)`` — what each collective puts on the wire (before
    any topology-aware lowering), summed per primitive name. The byte
    companion to ``count_collectives``; same axis_env contract."""
    mk = jax.make_jaxpr(fn, axis_env=axis_env) if axis_env else \
        jax.make_jaxpr(fn)
    closed = mk(*args, **kwargs)
    out: Dict[str, int] = {}
    for eqn in iter_eqns(closed.jaxpr):
        nm = eqn.primitive.name
        if nm not in COLLECTIVE_PRIMITIVES:
            continue
        nbytes = 0
        for v in eqn.invars:
            aval = getattr(v, "aval", None)
            shape = getattr(aval, "shape", None)
            dtype = getattr(aval, "dtype", None)
            if shape is not None and dtype is not None:
                nbytes += int(math.prod(shape)) * dtype.itemsize
        out[nm] = out.get(nm, 0) + nbytes
    return out


# ---------------------------------------------------------------------------
# Compiled-HLO collective accounting. GSPMD inserts collectives during
# SPMD partitioning, AFTER tracing — a jitted step's jaxpr shows none of
# them, so proving "zero1 lowers the gradient all-reduce to
# reduce-scatter + all-gather" requires reading the post-optimization
# HLO. Three spellings of this XLA are handled here:
# - XLA's ReduceScatterCreator runs on TPU/GPU only, so on CPU the
#   reduce-scatter appears as all-reduce followed by a partition
#   dynamic-slice (full result, then each replica keeps its 1/n).
#   ``hlo_collectives`` reclassifies that pair as ``reduce_scatter`` —
#   it IS the reduce-scatter this program lowers to on TPU — which keeps
#   the audit meaningful on the CPU CI box.
# - The all-reduce combiner merges a step's gradient all-reduces into
#   one op whose result is a tuple, one element per merged all-reduce;
#   each element counts as the all-reduce it was.
# - Operands are printed by name alone (``dynamic-slice(%param_0.3,
#   ...)``), and the slice usually sits inside a fusion: an operand's
#   shape is looked up where the name is defined.
# ---------------------------------------------------------------------------

_HLO_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

# one instruction that is a collective or a dynamic-slice: its result (an
# array, or a tuple of arrays: a combined collective, an async pair's
# state; a TPU layout's tiling nests one level of parentheses), op, and
# first operand (typed in older texts, a bare name now)
_HLO_OP_RE = re.compile(
    r"=\s*(?P<type>\((?:[^()]|\([^()]*\))*\)"
    r"|[a-z]+\d*\[[\d,]*\](?:\{[^}]*\})?)\s*"
    r"(?P<op>all-reduce|reduce-scatter|all-gather|all-to-all|"
    r"collective-permute|dynamic-slice)(?P<suffix>-start|-done)?"
    r"\((?:\s*(?:[a-z]+\d*\[(?P<oshape>[\d,]*)\](?:\{[^}]*\})?\s*)?"
    r"%?(?P<oname>[\w.-]+))?")
_HLO_ARRAY_RE = re.compile(r"(?P<dt>[a-z]+\d*)\[(?P<shape>[\d,]*)\]")
# where a name is defined as an array
_HLO_DEF_RE = re.compile(r"%([\w.-]+)\s*=\s*[a-z]+\d*\[([\d,]*)\]")


def _shape_elems(shape_str: str) -> int:
    return int(math.prod(int(d) for d in shape_str.split(",") if d))


def _hlo_module_text(obj: Any, *args, **kwargs) -> str:
    """Post-optimization HLO text from a str, a compiled executable
    (``jit(f).lower(...).compile()``), or a callable + example args
    (jitted or not — plain callables are wrapped in ``jax.jit``)."""
    if isinstance(obj, str):
        return obj
    if hasattr(obj, "lower"):                      # jitted function
        return obj.lower(*args, **kwargs).compile().as_text()
    if hasattr(obj, "compile"):                    # Lowered
        return obj.compile().as_text()
    if hasattr(obj, "as_text"):                    # Compiled executable
        return obj.as_text()
    return jax.jit(obj).lower(*args, **kwargs).compile().as_text()


def hlo_collectives(obj: Any, *args, reclassify_scatter: bool = True,
                    **kwargs) -> Dict[str, Dict[str, int]]:
    """Collectives in compiled HLO: ``{op: {count, bytes, max_bytes}}``
    with underscore op names (``all_reduce``, ``reduce_scatter``, ...);
    bytes are the op's OUTPUT buffer (sum / max over occurrences).
    Async ``-start``/``-done`` pairs count once, at the ``-done``, whose
    result is the collective's; a combined collective counts each
    element of its tuple result. With ``reclassify_scatter`` (default),
    an all-reduce whose full result feeds a dynamic-slice producing
    exactly 1/num_partitions of it is reported as ``reduce_scatter``
    (see module comment: XLA:CPU lacks the reduce-scatter combiner
    pass)."""
    text = _hlo_module_text(obj, *args, **kwargs)
    m = re.search(r"num_partitions=(\d+)", text)
    n_part = int(m.group(1)) if m else 1

    colls: List[Tuple[str, str, str]] = []   # (op, dtype, shape)
    # (out_shape, operand_shape or None, operand name)
    slices: List[Tuple[str, Optional[str], str]] = []
    for mo in _HLO_OP_RE.finditer(text):
        op, arrays = mo.group("op"), _HLO_ARRAY_RE.findall(mo.group("type"))
        if op == "dynamic-slice":
            slices.append((arrays[0][1], mo.group("oshape"),
                           mo.group("oname")))
        elif mo.group("suffix") != "-start":
            colls.extend((op, dt, shape) for dt, shape in arrays)

    # shapes some dynamic-slice cuts to exactly 1/num_partitions; a bare
    # operand's shape is read where it is defined, and only where some
    # all-reduce could be a scatter (one device's program has none)
    scattered = set()
    if reclassify_scatter and n_part > 1 and any(
            op == "all-reduce" for op, _, _ in colls):
        defined = (dict(_HLO_DEF_RE.findall(text))
                   if any(o is None for _, o, _ in slices) else {})
        for sh, osh, name in set(slices):
            osh = osh if osh is not None else defined.get(name)
            if osh is not None and \
                    _shape_elems(sh) * n_part == _shape_elems(osh):
                scattered.add(osh)

    out: Dict[str, Dict[str, int]] = {}
    for op, dt, shape in colls:
        if op == "all-reduce" and shape in scattered:
            op = "reduce-scatter"
        name = op.replace("-", "_")
        nbytes = _shape_elems(shape) * _HLO_DTYPE_BYTES.get(dt, 4)
        row = out.setdefault(name, {"count": 0, "bytes": 0, "max_bytes": 0})
        row["count"] += 1
        row["bytes"] += nbytes
        row["max_bytes"] = max(row["max_bytes"], nbytes)
    return out


def count_hlo_collectives(obj: Any, *args, **kwargs) -> Dict[str, int]:
    """``{op: count}`` view of ``hlo_collectives``."""
    return {op: row["count"]
            for op, row in hlo_collectives(obj, *args, **kwargs).items()}


def hlo_collective_bytes(obj: Any, *args, **kwargs) -> Dict[str, int]:
    """``{op: total_bytes}`` view of ``hlo_collectives``."""
    return {op: row["bytes"]
            for op, row in hlo_collectives(obj, *args, **kwargs).items()}


# --------------------------------------------------------------- audits
class Audit:
    """One registered structural check for ``tools/check.py --jaxpr``:
    trace ``fn(*args)``, measure peak/transfers/collectives, compare to
    the declared budgets. ``max_elements=None`` means unbounded (the
    reference rows exist to show the auditor SEES the blow-up)."""

    def __init__(self, name: str, fn: Callable, args: Tuple, *,
                 max_elements: Optional[int] = None,
                 max_transfers: Optional[int] = 0,
                 min_elements: Optional[int] = None,
                 extra: Optional[Callable[[], Tuple[bool, Dict]]] = None,
                 note: str = ""):
        self.name = name
        self.fn = fn
        self.args = args
        self.max_elements = max_elements
        self.max_transfers = max_transfers
        self.min_elements = min_elements
        self.extra = extra
        self.note = note

    def run(self) -> Dict[str, Any]:
        row: Dict[str, Any] = {"name": self.name, "note": self.note}
        try:
            row["peak_elements"] = peak_intermediate(self.fn, *self.args)
            row["transfers"] = count_transfers(self.fn, *self.args)
            row["collectives"] = count_collectives(self.fn, *self.args)
            ok = True
            if self.max_elements is not None:
                row["budget_elements"] = self.max_elements
                ok &= row["peak_elements"] <= self.max_elements
            if self.min_elements is not None:
                ok &= row["peak_elements"] >= self.min_elements
            if self.max_transfers is not None:
                ok &= row["transfers"] <= self.max_transfers
            if self.extra is not None:
                # audit-specific measurement (e.g. compiled-HLO
                # collective checks); its dict merges into the row
                extra_ok, extra_row = self.extra()
                row.update(extra_row)
                ok &= extra_ok
            row["ok"] = bool(ok)
        except Exception as e:  # noqa: BLE001 - a broken audit must report
            row["ok"] = False
            row["error"] = repr(e)
        return row


def builtin_audits() -> List[Audit]:
    """The registered step/postprocess functions with their structural
    budgets — the tentpole invariants, re-checkable on demand:

    - blocked NMS stays O(N·B) (the test_blocked_nms bound, N=4096);
    - the reference NMS row PROVES the auditor sees an N×N blow-up;
    - one-pass RoIAlign does <=8 gathers (one sampling pass);
    - the mnist train step traces with zero transfer primitives (the
      PR 1 sync-free contract, structural form);
    - interleaved two-tenant zoo dispatch leaves every engine's
      trace/compile counters exactly where warmup put them (the
      per-model zero-recompiles-after-warmup contract of serve/zoo.py);
    - (>= 2 devices only) the zero1 train step compiles to
      reduce-scatter + all-gather with no param-sized all-reduce, with
      the replicated step as the control row that DOES show the
      all-reduce zero1 replaced.
    """
    import jax.numpy as jnp

    from ..ops import nms as nms_ops
    from ..ops import roi_align as roi_ops

    audits: List[Audit] = []
    n, block = 4096, 256
    boxes = jnp.zeros((n, 4))
    scores = jnp.zeros((n,))
    audits.append(Audit(
        f"nms_blocked_n{n}",
        partial(nms_ops.nms_blocked, iou_threshold=0.5, max_out=100,
                block_size=block),
        (boxes, scores),
        max_elements=4 * n * block,
        note=f"O(N*B) budget, B={block}"))
    audits.append(Audit(
        f"nms_reference_n{n}",
        partial(nms_ops.nms_reference, iou_threshold=0.5, max_out=100),
        (boxes, scores),
        min_elements=n * n,
        note="control: auditor must SEE the N^2 buffer"))

    pyr = {f"p{lv}": jnp.zeros((64 >> (lv - 2), 64 >> (lv - 2), 8))
           for lv in (2, 3, 4, 5)}
    rois = jnp.zeros((16, 4))
    audits.append(Audit(
        "roi_align_onepass",
        partial(roi_ops.multiscale_roi_align),
        (pyr, rois),
        note="one-pass multiscale gather"))

    def train_step_audit() -> Audit:
        from ..core.registry import MODELS
        from ..train import TrainState, make_train_step
        from ..train.classification import make_loss_fn
        from ..train.optim import build_optimizer
        from ..train.schedules import build_schedule

        model = MODELS.build("mnist_fcn", num_classes=4,
                             dtype=jnp.float32)
        params = model.init(jax.random.key(0),
                            jnp.zeros((1, 16, 16, 1)))["params"]
        tx = build_optimizer("sgd", build_schedule("constant",
                                                   base_lr=1e-2),
                             params=params)
        state = TrainState.create(apply_fn=model.apply, params=params,
                                  tx=tx)
        batch = {"image": jnp.zeros((8, 16, 16, 1)),
                 "label": jnp.zeros((8,), jnp.int32)}
        step = make_train_step(make_loss_fn(), donate=False)
        rng = jax.random.key(0)
        return Audit("train_step_mnist", step, (state, batch, rng),
                     max_transfers=0,
                     note="hot-loop step: zero transfer primitives")

    audits.append(train_step_audit())

    def zoo_multimodel_audit() -> Audit:
        import numpy as np

        from ..serve import MicroBatcher, ModelZoo

        def extra():
            zoo = ModelZoo()
            for alias in ("a", "b"):
                zoo.register(alias, "mnist_fcn", num_classes=4,
                             image_size=16, batch_buckets=(1, 2))
                zoo.load(alias, wait=True)
            warm = {a: (zoo.engine(a).trace_count,
                        zoo.engine(a).compile_count) for a in ("a", "b")}
            img = np.zeros((16, 16, 3), np.float32)
            with MicroBatcher(zoo=zoo, max_wait_ms=1.0) as mb:
                handles = [mb.submit(img, model=("a", "b")[i % 2])
                           for i in range(8)]
                for h in handles:
                    h.result(timeout=120.0)
            ok, row = True, {}
            for a in ("a", "b"):
                eng = zoo.engine(a)
                row[f"{a}_trace_count"] = eng.trace_count
                row[f"{a}_compile_count"] = eng.compile_count
                ok &= (eng.trace_count, eng.compile_count) == warm[a]
            return ok, row

        # the traced fn is a placeholder; the audit's substance is the
        # extra() pass driving interleaved dispatch through two warm
        # engines and asserting their counters never move
        return Audit("zoo_multimodel", lambda x: x + 1,
                     (jnp.zeros((1,)),), extra=extra,
                     note="interleaved 2-tenant dispatch: zero "
                          "retraces after warmup")

    audits.append(zoo_multimodel_audit())

    def zero1_audits() -> List[Audit]:
        from ..core.registry import MODELS
        from ..parallel.mesh import MeshConfig, build_mesh
        from ..train import TrainState, make_train_step
        from ..train.classification import make_loss_fn
        from ..train.optim import build_optimizer
        from ..train.schedules import build_schedule
        from ..train.steps import shard_state

        mesh = build_mesh(MeshConfig(data=-1))
        n_dev = mesh.shape["data"] * mesh.shape["fsdp"]

        def fresh(zero1: bool) -> TrainState:
            model = MODELS.build("mnist_fcn", num_classes=4,
                                 dtype=jnp.float32)
            params = model.init(jax.random.key(0),
                                jnp.zeros((1, 16, 16, 1)))["params"]
            tx = build_optimizer(
                "adamw", build_schedule("constant", base_lr=1e-3),
                params=params)
            state = TrainState.create(apply_fn=model.apply,
                                      params=params, tx=tx)
            return shard_state(state, mesh, zero1=zero1)

        batch = {"image": jnp.zeros((8 * n_dev, 16, 16, 1)),
                 "label": jnp.zeros((8 * n_dev,), jnp.int32)}
        rng = jax.random.key(0)
        out: List[Audit] = []

        for mode in ("zero1", "replicated"):
            state = fresh(zero1=(mode == "zero1"))
            step = make_train_step(make_loss_fn(), mesh=mesh,
                                   donate=False, weight_update=mode)
            # the biggest param leaf is the threshold for "param-sized":
            # any all-reduce at or above it means the gradient
            # all-reduce survived; smaller ones are the non-divisible
            # tail and scalar metric reductions
            param_bytes = max(
                int(math.prod(p.shape)) * p.dtype.itemsize
                for p in jax.tree.leaves(state.params))

            def extra(step=step, state=state, mode=mode,
                      param_bytes=param_bytes):
                hlo = hlo_collectives(step, state, batch, rng)
                row = {"hlo_collectives":
                       {op: r["count"] for op, r in hlo.items()},
                       "collective_bytes":
                       {op: r["bytes"] for op, r in hlo.items()}}
                ar_max = hlo.get("all_reduce", {}).get("max_bytes", 0)
                if mode == "zero1":
                    ok = (hlo.get("reduce_scatter", {}).get("count", 0) >= 1
                          and hlo.get("all_gather", {}).get("count", 0) >= 1
                          and ar_max < param_bytes)
                else:
                    ok = ar_max >= param_bytes
                return ok, row

            out.append(Audit(
                f"train_step_{mode}_dp{n_dev}", step,
                (fresh(zero1=(mode == "zero1")), batch, rng),
                max_transfers=0, extra=extra,
                note=("grad AR lowered to reduce-scatter + all-gather"
                      if mode == "zero1" else
                      "control: full-gradient all-reduce present")))
        return out

    if len(jax.devices()) >= 2:
        audits.extend(zero1_audits())
    return audits


def run_audits(audits: Optional[List[Audit]] = None
               ) -> List[Dict[str, Any]]:
    if audits is None:
        audits = builtin_audits()
    return [a.run() for a in audits]
