"""Profiling helpers: the peak table, the retrace guard, XLA cost analysis.

- ``PEAK_BF16_FLOPS`` / ``device_peak_flops``: the program's one table of
  per-chip peaks, keyed by ``device_kind``; an unlisted device is an error.
- ``RetraceGuard``: warns when a jitted step sees a new argument signature.
- ``compiled_flops`` / ``model_info``: what XLA's ``cost_analysis()`` counts
  for a compiled function (export and the model summaries read it).

No timing lives here: a speed is measured by ``benchmarks/run.py`` through
``Trainer.train`` on the chip (PERF.md).
"""

from __future__ import annotations

import warnings
from typing import Callable, Dict, Optional

import jax

# The one peak table: per-chip dense bf16 FLOP/s keyed by the exact
# ``device_kind`` JAX reports (Google Cloud TPU documentation, the
# per-version system-architecture pages). Every MFU in the repo divides
# by this; a device that is not listed is an error, never a default.
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,     # v5e
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,     # v6e (Trillium)
}


def device_peak_flops(device: Optional[jax.Device] = None) -> float:
    device = device or jax.devices()[0]
    kind = device.device_kind
    if kind not in PEAK_BF16_FLOPS:
        raise ValueError(
            f"no bf16 peak known for device_kind {kind!r} (platform "
            f"{device.platform!r}); known: {sorted(PEAK_BF16_FLOPS)}")
    return PEAK_BF16_FLOPS[kind]


class RetraceGuard:
    """Warn when a wrapped (jitted) step function sees a NEW abstract
    argument signature after its first call — under ``jax.jit`` every new
    shape/dtype/treedef signature forces a full XLA retrace, and a
    retrace mid-epoch (shape churn from a sloppy loader, a dtype flip, a
    non-dropped last batch) is the silent MFU killer: minutes of compile
    amortized over zero extra steps.

    Signatures are computed host-side from leaf shapes/dtypes (python
    scalars hash by type, matching jit's weak-typed cache key), so the
    guard costs a tree-flatten per call and never touches the device.
    Deliberate shape buckets (multiscale training) warn once per new
    bucket and then stay quiet.
    """

    def __init__(self, fn: Callable, name: str = "step",
                 logger=None, max_warnings: int = 8,
                 on_retrace: Optional[Callable[[Dict], None]] = None):
        self.fn = fn
        self.name = name
        self.logger = logger
        self.max_warnings = max_warnings
        # observability hook: called with {name, retraces, n_signatures}
        # on every retrace (the Trainer routes it into the flight
        # recorder ring) — fires even past the max_warnings cap
        self.on_retrace = on_retrace
        self._sigs: set = set()
        self.retraces = 0          # new signatures seen after the first

    @property
    def n_signatures(self) -> int:
        return len(self._sigs)

    @staticmethod
    def _leaf_sig(x):
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is None and dtype is None:
            return type(x).__name__
        return (tuple(shape) if shape is not None else None, str(dtype))

    def _signature(self, args, kwargs):
        leaves, treedef = jax.tree.flatten((args, kwargs))
        return (str(treedef), tuple(self._leaf_sig(l) for l in leaves))

    def __call__(self, *args, **kwargs):
        sig = self._signature(args, kwargs)
        if sig not in self._sigs:
            self._sigs.add(sig)
            if len(self._sigs) > 1:
                self.retraces += 1
                if self.on_retrace is not None:
                    self.on_retrace({"name": self.name,
                                     "retraces": self.retraces,
                                     "n_signatures": len(self._sigs)})
                if self.retraces <= self.max_warnings:
                    msg = (f"{self.name}: argument signature changed "
                           f"({len(self._sigs)} distinct signatures seen) "
                           "— each new shape/dtype forces an XLA retrace; "
                           "pad or bucket inputs to fixed shapes")
                    warnings.warn(msg, RuntimeWarning, stacklevel=2)
                    if self.logger is not None:
                        self.logger.warning(msg)
        return self.fn(*args, **kwargs)


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` as a dict (``{}`` when the backend
    reports none)."""
    return compiled.cost_analysis() or {}


def compiled_flops(fn: Callable, *args) -> float:
    from ..obs.xla import tracked_compile   # lazy: obs imports this module
    compiled = tracked_compile(jax.jit(fn).lower(*args),
                               getattr(fn, "__name__", "flops_probe"))
    return float(cost_analysis_dict(compiled).get("flops", 0.0))


def model_info(model, *example_args, train: bool = False,
               tabulate: bool = False, **example_kw) -> Dict[str, float]:
    """Params / FLOPs / activation summary for a flax model — the
    get_model_info / model_info surface (yolov5 utils/torch_utils.py:236,
    YOLOX yolox/utils/model_utils.py, vision_transformer/flops.py).

    FLOPs come from XLA's compiled cost analysis of the forward (so
    fusion is reflected, like thop/fvcore count the traced graph). Set
    ``tabulate=True`` to also return flax's per-layer table string."""
    import jax.numpy as jnp
    import numpy as np

    variables = model.init(jax.random.key(0), *example_args,
                           train=train, **example_kw)
    n_params = sum(int(np.prod(np.shape(l)))
                   for l in jax.tree.leaves(variables["params"]))
    flops = compiled_flops(
        lambda v, *a: model.apply(v, *a, train=train, **example_kw),
        variables, *example_args)
    info: Dict[str, float] = {
        "params_m": n_params / 1e6,
        "gflops": flops / 1e9,
    }
    if tabulate:
        import flax.linen as nn
        info["table"] = nn.tabulate(
            model, jax.random.key(0),
            compute_flops=False, compute_vjp_flops=False)(
            *example_args, train=train, **example_kw)
    return info
