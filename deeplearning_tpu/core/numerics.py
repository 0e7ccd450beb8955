"""Trace-time numerics mode: fast-TPU defaults vs exact-torch parity.

Round 4 switched ViT/Swin/ConvNeXt to exact-erf GELU for torch parity
(reference uses ``torch.nn.GELU()`` = erf, e.g.
classification/vision_transformer/vit_model.py:114) asserting the cost was
~0 because "the elementwise op fuses either way". Round 5 measured it on a
TPU v5e: the erf lowering cost **3.8 MFU points** on the ViT-B/16 train
step at batch 128 (July, another runtime; not measured on this one),
because XLA lowers erf to a long polynomial while tanh uses the fast
rational approximation.

Policy: training defaults to the tanh approximation (max abs deviation from
erf-GELU is ~1e-3, irrelevant to SGD); weight-port / reference-parity paths
enable exact mode. The flag is read at **trace time** only, so wrap
``model.init`` / ``model.apply`` (or the jit that traces them) — flipping it
after a function is compiled has no effect on the cached executable.

Usage:
    from deeplearning_tpu.core import numerics
    y = numerics.gelu(x)                 # in a flax module

    with numerics.exact_numerics():      # parity tests / torch-weight eval
        out = model.apply(variables, x)

    tools/train.py: ``model.exact_gelu=true`` sets the mode process-wide.
"""

from __future__ import annotations

import contextlib
from typing import Iterator

import flax.linen as nn
import jax

_EXACT = False


def exact_enabled() -> bool:
    return _EXACT


def set_exact(flag: bool) -> None:
    """Process-wide switch (CLI entry points). Prefer the context manager."""
    global _EXACT
    _EXACT = bool(flag)


@contextlib.contextmanager
def exact_numerics(flag: bool = True) -> Iterator[None]:
    """Temporarily select exact-torch numerics for anything traced inside."""
    global _EXACT
    old = _EXACT
    _EXACT = bool(flag)
    try:
        yield
    finally:
        _EXACT = old


def gelu(x: jax.Array) -> jax.Array:
    """GELU honoring the numerics mode.

    exact mode → erf (matches torch nn.GELU() bit-for-bit in f32);
    default   → tanh approximation (fast TPU lowering, measured above).
    """
    return nn.gelu(x, approximate=not _EXACT)
