"""Profiling: step timing, XLA-FLOPs MFU meter.

The reference's ad-hoc timing stack (SURVEY.md §5: cuda-synchronized
time_sync, thop-based layer profilers, swin throughput mode) becomes:
- ``StepTimer``: wall-clock per-step timing; the caller syncs with
  ``jax.block_until_ready`` before ``stop()``.
- ``mfu``: measured step time vs compiled-graph FLOPs vs chip peak — the
  BASELINE.md headline metric.
"""

from __future__ import annotations

import time
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


class StepTimer:
    """Accumulates step wall times; caller syncs before ``stop()``."""

    def __init__(self):
        self.times = []
        self._t0 = None

    def start(self):
        self._t0 = time.perf_counter()

    def stop(self):
        # stop() without a matching start() (callback fired before the
        # loop primed the timer) records nothing instead of raising a
        # TypeError on the None arithmetic
        if self._t0 is None:
            return
        self.times.append(time.perf_counter() - self._t0)
        self._t0 = None

    @property
    def mean(self) -> float:
        return sum(self.times) / max(len(self.times), 1)


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


def measure_mfu(step_fn: Callable, args: tuple, n_steps: int = 10
                ) -> Dict[str, float]:
    """Run ``step_fn(*args)`` n times on the default device and report
    step time + MFU against that device's peak. Raises before running
    anything on a device the peak table does not know."""
    peak = device_peak_flops()
    flops = compiled_flops(step_fn, *args)
    jax.block_until_ready(step_fn(*args))
    t0 = time.perf_counter()
    for _ in range(n_steps):
        out = step_fn(*args)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / n_steps
    return {"step_time_s": dt, "flops_per_step": flops,
            "mfu": flops / dt / peak if flops else 0.0,
            "peak_flops": peak}


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
