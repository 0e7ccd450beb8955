"""``mellum`` (Mellum2-12B-A2.5B): a causal decoder with grouped-query
attention, sliding-window layers among full ones, and softmax-routed sparse
experts in every layer. Training path only: no key/value cache.

With ``x`` a token's hidden state and ``RMSNorm`` at eps 1e-6, no biases:

- block: ``h = x + Attn(RMSNorm(x))``, ``out = h + MoE(RMSNorm(h))``.
- Attn: ``num_attention_heads`` query heads and ``num_key_value_heads``
  key/value heads of width ``head_dim``; query head ``j`` reads key/value head
  ``j // (heads / kv heads)``; a per-head RMSNorm on ``q`` and on ``k``, then
  the rotary embedding over all of ``head_dim`` (pairs ``(i, i + d/2)``);
  scores ``q . k / sqrt(head_dim)``, softmax in float32. A layer's kind
  (``layer_types[i]``) sets the mask and the rotary frequencies: a
  ``sliding_attention`` layer lets query ``i`` see keys ``i - window + 1 ..
  i`` at the unscaled frequencies; a ``full_attention`` layer sees keys ``0 ..
  i`` at YaRN's frequencies (``yarn_inv_freq``), cos and sin times the
  attention factor.
- MoE: ``parallel/moe.py::HeldExpertsMlp`` with ``softmax_route``: softmax
  over all the published experts in float32, the ``num_experts_per_tok``
  largest, their weights normalised over the chosen; no shared expert, no
  bias, no scale.

A chip holds its share of a stated deployment: ``experts_held`` of
``num_experts`` a layer from ``first_expert`` on, ``vocab_size`` rows of the
vocabulary. The attention core picks its own path
(``ops/pallas/flash_attention.py::select_path``) and runs under the scope
``sliding_core`` or ``full_core``; each layer tallies a ``kernel`` flight
event (``gqa_attention``) with the path it took, its window and
``forward_kept``: whether the block's backward pass finds the core's output
and logsumexp kept (``decoder.remat_block``: all else in a block is computed
again). The skeleton round the blocks is ``decoder.py``'s, shared with
``glm_moe_lite.py``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ...obs import flight
from ...ops.pallas import flash_attention as fused
from ...parallel.moe import HeldExpertsMlp, softmax_route
from .decoder import (RMSNorm, _dense, _factory, forward_kept, remat_block,
                      rotary)

SLIDING, FULL = "sliding_attention", "full_attention"


def plain_inv_freq(theta: float, r: int) -> np.ndarray:
    """The unscaled frequencies of ``r`` rotary dimensions: theta^(-2i/r)."""
    return theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)


def yarn_inv_freq(theta: float, r: int, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's frequencies of ``r`` rotary dimensions: dimension ``i`` keeps
    ``theta^(-2i/r)`` where it turns more than ``beta_fast`` times over the
    ``original`` length, is divided by ``factor`` where it turns fewer than
    ``beta_slow`` times, and is blended linearly between the two (the ramp
    over whole dimensions)."""
    e = plain_inv_freq(theta, r)

    def dim_of(turns):
        return r * math.log(original / (2 * math.pi * turns)) \
            / (2 * math.log(theta))
    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), r - 1)
    ramp = np.clip((np.arange(r // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (e / factor) * ramp + e * (1 - ramp)


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    """``config.json``'s keys, with the chip's share beside them."""
    vocab_size: int = 98304
    hidden_size: int = 2304
    num_hidden_layers: int = 28
    # a period of layer kinds, repeated over the depth
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL)
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    rope_theta: float = 500000.0
    yarn_factor: float = 16.0
    yarn_original_max_position_embeddings: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.2772588722239782
    moe_intermediate_size: int = 896
    num_experts: int = 64
    experts_held: int = 64
    first_expert: int = 0
    num_experts_per_tok: int = 8
    rms_norm_eps: float = 1e-6

    def kind(self, i: int) -> str:
        return self.layer_types[i % len(self.layer_types)]

    def block(self, i: int, dtype, name: str):
        return _RematBlock(self, self.kind(i), dtype, name=name)

    def mtp(self, dtype, name: str):
        return None       # the config gives the family no such module


class GQAttention(nn.Module):
    """Grouped-query attention of one layer ``kind``."""
    cfg: MellumConfig
    kind: str
    dtype: Any = jnp.bfloat16

    def rope(self) -> Tuple[jax.Array, float, Optional[int]]:
        """(rotary frequencies, cos/sin factor, window) of this kind."""
        c = self.cfg
        if self.kind == SLIDING:
            return (jnp.asarray(plain_inv_freq(c.rope_theta, c.head_dim),
                                jnp.float32), 1.0, c.sliding_window)
        return jnp.asarray(yarn_inv_freq(
            c.rope_theta, c.head_dim, c.yarn_factor,
            c.yarn_original_max_position_embeddings, c.yarn_beta_fast,
            c.yarn_beta_slow), jnp.float32), c.yarn_attention_factor, None

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        b, n, _ = x.shape
        h, kv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        inv_freq, factor, window = self.rope()

        # ``head_split`` (train/steps.py::STEP_SCOPES): the layout work at
        # the core's boundary that is the model's own
        def heads(name, count):
            y = _dense(count * d, self.dtype, name)(x)
            with jax.named_scope("head_split"):
                return y.reshape(b, n, count, d).transpose(0, 2, 1, 3)
        norm = lambda name: RMSNorm(c.rms_norm_eps, self.dtype, name=name)
        q = rotary(norm("q_norm")(heads("q", h)), inv_freq, factor)
        k = rotary(norm("k_norm")(heads("k", kv)), inv_freq, factor)
        v = heads("v", kv)
        path = fused.select_path(n, d, initializing=self.is_initializing())
        kept = forward_kept(self, path)
        flight.tally("kernel",
                     ("gqa_attention", path, kept, window, n, h, kv, d),
                     member="/".join(self.path), name="gqa_attention",
                     path=path, window=window, shape=[b, h, kv, n, d],
                     forward_kept=kept)
        with jax.named_scope("sliding_core" if window else "full_core"):
            out = fused.causal_attention(q, k, v, d ** -0.5, path, window)
        with jax.named_scope("head_split"):
            out = out.transpose(0, 2, 1, 3).reshape(b, n, h * d)
        return _dense(c.hidden_size, self.dtype, "o")(out)


class MellumBlock(nn.Module):
    cfg: MellumConfig
    kind: str
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        norm = lambda name: RMSNorm(c.rms_norm_eps, self.dtype, name=name)
        h = x + GQAttention(c, self.kind, self.dtype, name="attn")(
            norm("attn_norm")(x))
        moe = HeldExpertsMlp(
            num_experts=c.num_experts, held=c.experts_held,
            first=c.first_expert, top_k=c.num_experts_per_tok,
            hidden=c.moe_intermediate_size, shared_experts=0,
            route=softmax_route, dtype=self.dtype, name="moe")
        return h + moe(norm("ffn_norm")(h))


_RematBlock = remat_block(MellumBlock)


# Mellum2-12B-A2.5B (``MellumConfig``'s defaults are its published config,
# which no single chip trains) as one chip's share of 4-way expert
# parallelism: experts 0-15 of 64 and rows 0-24,575 of the vocabulary, layers
# 0-3 (one whole period: three sliding, one full; the other 24 lie on six
# further groups of chips as pipeline stages)
mellum2_ep4 = _factory("mellum2_ep4", MellumConfig, vocab_size=24576,
                       num_hidden_layers=4, experts_held=16)
# a CPU-sized decoder of the same shape of block: both layer kinds, grouped
# heads, a window shorter than a test's sequence, YaRN on
mellum_micro = _factory(
    "mellum_micro", MellumConfig, vocab_size=512, hidden_size=64,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, sliding_window=8, yarn_original_max_position_embeddings=16,
    moe_intermediate_size=48, num_experts=16, experts_held=4,
    num_experts_per_tok=4)
