"""deeplearning_tpu — a TPU-native deep-learning framework.

A ground-up JAX/XLA/Pallas rebuild of the capabilities of the
KKKSQJ/DeepLearning paper-reimplementation zoo (reference mounted at
/root/reference). Where the reference copy-pastes per-project CUDA/DDP
harnesses, this package provides ONE shared TPU-first core:

- ``core``      config tree (dataclass + YAML + CLI), registry, logging,
                Orbax checkpointing, RNG, precision policy.
- ``parallel``  device mesh construction, GSPMD shardings, collectives,
                ring attention (sequence parallelism).
- ``ops``       Pallas kernels + XLA-friendly fixed-shape ops (window
                attention, NMS, RoIAlign, focal loss, box coders).
- ``models``    the model zoo (classification / detection / segmentation /
                self-supervised / metric learning / pose / stereo).
- ``data``      input pipelines (per-host sharded loading, mixup/mosaic).
- ``train``     TrainState, hook-based Trainer, optimizers, LR schedules.
- ``evaluation``  metrics: top-k, confusion-matrix mIoU, dice, COCO/VOC
                mAP (with a native C++ fast path), CMC/mAP retrieval.
- ``export``    StableHLO / TF SavedModel export paths.
- ``analysis``  dltpu-check: AST policy linter with a ratchet baseline,
                jaxpr structural auditor, runtime strict mode.
"""

import time as _time

_T0 = _time.perf_counter()    # start of the "setup/import" phase, below

__version__ = "0.1.0"

# Importing the subpackages populates the registries (models, optimizers,
# schedules, ...), so `deeplearning_tpu.core.MODELS.build(name)` works after
# a bare `import deeplearning_tpu`.
from . import core, ops, parallel, data, train, models, evaluation  # noqa: E402,F401
from . import analysis  # noqa: E402,F401  (lint is stdlib-only; jaxpr/strict lazy)

# The package's own import cost (every model family, flax, optax, orbax),
# as a run-level phase: `obs.spans.phases()` and the benchmark's
# `program_import_s` read it. JAX itself may have been imported before.
from .obs import spans as _spans  # noqa: E402

_spans.record_phase("setup/import", _T0)
