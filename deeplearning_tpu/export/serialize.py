"""Model export: StableHLO serialization + TF SavedModel + param I/O.

The deployment-path successor (SURVEY.md L7): where the reference exports
TorchScript/ONNX/TensorRT/CoreML (yolov5 export.py:29-159, YOLOX
tools/export_onnx.py, others/deploy/*), the TPU-era flow is:

- ``export_stablehlo``: jax.export → portable StableHLO bytes (the IR
  every XLA-based runtime consumes; the ONNX analog).
- ``export_savedmodel``: jax2tf → TF SavedModel (the TF-serving /
  TFLite-converter entry; replaces the TensorRT engine-build path).
- RepVGG deploy conversion is models/classification/repvgg.reparameterize
  (structural re-param, convert.py analog).
"""

from __future__ import annotations

import os
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def export_stablehlo(fn: Callable, example_args: Sequence[Any],
                     path: Optional[str] = None) -> bytes:
    """Serialize a jittable fn to portable StableHLO bytes; reload with
    ``load_stablehlo``."""
    exported = jax.export.export(jax.jit(fn))(*example_args)
    blob = exported.serialize()
    if path:
        with open(path, "wb") as f:
            f.write(blob)
    return blob


def load_stablehlo(blob: bytes) -> Callable:
    exported = jax.export.deserialize(blob)
    return exported.call


def export_savedmodel(fn: Callable, example_args: Sequence[Any],
                      path: str) -> bool:
    """jax2tf → tf.saved_model.save. Returns False when TF is absent."""
    try:
        import tensorflow as tf
        from jax.experimental import jax2tf
    except ImportError:
        return False
    tf_fn = tf.function(
        jax2tf.convert(fn, with_gradient=False),
        autograph=False,
        input_signature=[
            tf.TensorSpec(np.shape(a), np.asarray(a).dtype, name=f"arg{i}")
            for i, a in enumerate(example_args)])
    module = tf.Module()
    module.f = tf_fn
    # explicit serving signature so native runners (C API,
    # native/savedmodel_runner.cc) find serving_default_arg0 /
    # StatefulPartitionedCall ops
    tf.saved_model.save(
        module, path,
        signatures={"serving_default": tf_fn.get_concrete_function()})
    return True


def flops_estimate(fn: Callable, *example_args) -> float:
    """Compiled-graph FLOPs from XLA cost analysis — the thop/fvcore
    FLOPs-counter successor (vision_transformer/flops.py, yolov5
    torch_utils.py:104). Delegates to utils/profiling.compiled_flops."""
    from ..utils.profiling import compiled_flops
    return compiled_flops(fn, *example_args)
