"""``granitemoehybrid`` without experts (Granite 4.0-H Micro): a causal
decoder of Mamba-2 state-space layers with a full attention layer among
every ten, no positional embedding, a dense SwiGLU MLP in every layer, a tied
head and μP multipliers. Training path only: no recurrent state and no
key/value cache are kept between calls.

With ``x`` a token's hidden state, ``RMSNorm`` at ``rms_norm_eps`` and no
biases but the convolution's:

- model: ``h₀ = embedding_multiplier · Embed(tokens)``, the blocks, a final
  RMSNorm; the head is the embedding: ``logits = RMSNorm(h) Embedᵀ /
  logits_scaling``.
- block: ``h = x + residual_multiplier · Mixer(RMSNorm(x))``, ``out = h +
  residual_multiplier · MLP(RMSNorm(h))``; ``MLP(u) = W_out(silu(a) ⊙ b)``
  with ``[a, b] = W_in u``.
- Mixer where ``layer_types[i]`` is ``mamba`` (Mamba-2): ``[z, xBC, dt] = u
  W_in``; ``xBC ← silu(conv(xBC))``, a causal depthwise convolution of
  ``mamba_d_conv`` positions with its bias; ``[x, B, C] = xBC``; ``Δ =
  softplus(dt + dt_bias)``, ``A = −exp(A_log)``; the recurrence of
  ``ops/ssd.py`` over ``mamba_n_heads`` heads of ``mamba_d_head`` and a state
  of ``mamba_d_state``, B and C shared by the heads of each of
  ``mamba_n_groups`` groups; ``y ← y + D x``; ``y ← RMSNorm_w(y ⊙ silu(z))``
  over all of ``y``; ``out = y W_out``.
- Mixer where it is ``attention``: ``num_attention_heads`` query heads and
  ``num_key_value_heads`` key/value heads of ``hidden_size /
  num_attention_heads``, query head ``j`` reading key/value head ``j // (heads
  / kv heads)``; no positional embedding; causal over every earlier key;
  scores ``attention_multiplier · q·k``, softmax in float32.

A chip holds its share of a stated deployment: ``vocab_size`` rows of the
tied embedding and head. The scan runs under the scope ``ssd_scan`` (from
``Δ`` and ``A`` to ``y`` before the skip), the convolution and its silu under
``short_conv``, the skip, the gate and the norm under ``gated_norm``; each
Mamba layer tallies a ``kernel`` flight event ``ssd_scan`` (path ``fused``
or ``lax``, shape ``[B, N, H, P, S, chunk]``): the scan takes the Pallas
kernels of ``ops/pallas/ssd_scan.py`` where its ``select_path`` says so and
``ops/ssd.py``'s ``jax.numpy`` form elsewhere. The attention core picks its
own path (``ops/pallas/flash_attention.py::select_path``) and runs under
``full_core``, tallying ``gqa_attention`` as Mellum2's does. The skeleton
round the blocks is ``decoder.py``'s.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ...obs import flight
from ...ops import ssd
from ...ops.pallas import flash_attention as fused
from ...ops.pallas import ssd_scan as fused_scan
from .decoder import RMSNorm, _dense, _factory, forward_kept, remat_block

MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class GraniteConfig:
    """``config.json``'s keys, with the chip's share beside them."""
    vocab_size: int = 100352
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    # a period of layer kinds, repeated over the depth
    layer_types: Tuple[str, ...] = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    intermediate_size: int = 8192           # shared_intermediate_size
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    tie_word_embeddings: bool = True
    rms_norm_eps: float = 1e-5

    def kind(self, i: int) -> str:
        return self.layer_types[i % len(self.layer_types)]

    def block(self, i: int, dtype, name: str):
        return _RematBlock(self, self.kind(i), dtype, name=name)

    def mtp(self, dtype, name: str):
        return None       # the config gives the family no such module


def _a_log_init(key, shape, dtype=jnp.float32):
    """Mamba-2's draw: ``A`` uniform in [1, 16], kept as its log."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _conv_init(fan_in: int):
    """PyTorch's draw for a convolution's kernel and bias, Mamba-2's: uniform
    in ±1/sqrt(fan_in), the depthwise kernel's fan-in its width."""
    bound = fan_in ** -0.5
    return lambda key, shape, dtype=jnp.float32: jax.random.uniform(
        key, shape, dtype, -bound, bound)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """Mamba-2's draw: ``Δ`` log-uniform in [0.001, 0.1] through the inverse
    softplus, so that ``softplus(dt_bias)`` is that ``Δ``."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                    math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class MambaMixer(nn.Module):
    """One Mamba-2 mixer (the module docstring's Mixer of a ``mamba``
    layer)."""
    cfg: GraniteConfig
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        c = self.cfg
        bsz, n, _ = u.shape
        h, p, s, g = (c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state,
                      c.mamba_n_groups)
        inner, conv_dim = h * p, h * p + 2 * g * s
        zxbcdt = _dense(inner + conv_dim + h, self.dtype, "in_proj")(u)
        z, xbc, dt = jnp.split(zxbcdt, [inner, inner + conv_dim], axis=-1)
        conv_init = _conv_init(c.mamba_d_conv)
        conv_kernel = self.param("conv_kernel", conv_init,
                                 (c.mamba_d_conv, conv_dim), jnp.float32)
        conv_bias = self.param("conv_bias", conv_init, (conv_dim,),
                               jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (h,), jnp.float32)
        a_log = self.param("A_log", _a_log_init, (h,), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (h,), jnp.float32)
        norm_scale = self.param("norm_scale", nn.initializers.ones, (inner,),
                                jnp.float32)
        with jax.named_scope("short_conv"):
            xbc = jax.nn.silu(ssd.causal_conv1d(xbc, conv_kernel, conv_bias)
                              ).astype(self.dtype)
        x, b, cc = jnp.split(xbc, [inner, inner + g * s], axis=-1)
        x = x.reshape(bsz, n, h, p)
        delta = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
        path = fused_scan.select_path(n, h, p, s, g, c.mamba_chunk_size,
                                      x.dtype,
                                      initializing=self.is_initializing())
        flight.tally("kernel", ("ssd_scan", path, n, h, p, s, g,
                                c.mamba_chunk_size),
                     member="/".join(self.path), name="ssd_scan", path=path,
                     shape=[bsz, n, h, p, s, c.mamba_chunk_size])
        scan = fused_scan.ssd_scan if path == "fused" else ssd.ssd_scan
        y = scan(x, delta, -jnp.exp(a_log), b.reshape(bsz, n, g, s),
                 cc.reshape(bsz, n, g, s), c.mamba_chunk_size)
        with jax.named_scope("gated_norm"):
            y = y.astype(jnp.float32) + x.astype(jnp.float32) * skip[:, None]
            y = ssd.gated_rms_norm(y.reshape(bsz, n, inner), z, norm_scale,
                                   c.rms_norm_eps).astype(self.dtype)
        return _dense(c.hidden_size, self.dtype, "out_proj")(y)


class NoPEAttention(nn.Module):
    """Grouped-query causal attention with no positional embedding."""
    cfg: GraniteConfig
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        b, n, _ = x.shape
        h, kv = c.num_attention_heads, c.num_key_value_heads
        d = c.hidden_size // h

        # ``head_split`` (train/steps.py::STEP_SCOPES): the layout work at
        # the core's boundary that is the model's own
        def heads(name, count):
            y = _dense(count * d, self.dtype, name)(x)
            with jax.named_scope("head_split"):
                return y.reshape(b, n, count, d).transpose(0, 2, 1, 3)
        q, k, v = heads("q", h), heads("k", kv), heads("v", kv)
        path = fused.select_path(n, d, initializing=self.is_initializing())
        kept = forward_kept(self, path)
        flight.tally("kernel", ("gqa_attention", path, kept, None, n, h, kv, d),
                     member="/".join(self.path), name="gqa_attention",
                     path=path, window=None, shape=[b, h, kv, n, d],
                     forward_kept=kept)
        with jax.named_scope("full_core"):
            out = fused.causal_attention(q, k, v, c.attention_multiplier, path)
        with jax.named_scope("head_split"):
            out = out.transpose(0, 2, 1, 3).reshape(b, n, h * d)
        return _dense(c.hidden_size, self.dtype, "o")(out)


def _residual(x, y, multiplier: float):
    """``x + multiplier · y`` with the product taken in float32 and rounded
    once: a Python float times a bfloat16 array is a bfloat16 product, in
    which 0.22 is 0.2197, and every block's part of the residual stream would
    shrink by the same 0.12 % (the benchmark cell's loss sat 0.066 % off its
    float32 reference on every seed on a TPU v5e)."""
    return x + (multiplier * y.astype(jnp.float32)).astype(x.dtype)


class GraniteBlock(nn.Module):
    cfg: GraniteConfig
    kind: str
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        norm = lambda name: RMSNorm(c.rms_norm_eps, self.dtype, name=name)
        mixer = MambaMixer(c, self.dtype, name="mixer") if self.kind == MAMBA \
            else NoPEAttention(c, self.dtype, name="attn")
        h = _residual(x, mixer(norm("mixer_norm")(x)), c.residual_multiplier)
        a, gate = jnp.split(_dense(2 * c.intermediate_size, self.dtype,
                                   "input_linear")(norm("ffn_norm")(h)), 2,
                            axis=-1)
        mlp = _dense(c.hidden_size, self.dtype, "output_linear")(
            jax.nn.silu(a) * gate)
        return _residual(h, mlp, c.residual_multiplier)


_RematBlock = remat_block(GraniteBlock)


# Granite 4.0-H Micro (``GraniteConfig``'s defaults are its published config)
# as one pipeline stage of four: layers 0-9 (one whole period: five Mamba
# layers, the attention layer, four Mamba layers), with rows 0-12,543 of the
# 100,352-row tied vocabulary (8 chips share the embedding and head)
granite4_h_micro_vocab8 = _factory("granite4_h_micro_vocab8", GraniteConfig,
                                   vocab_size=12544, num_hidden_layers=10)
# a CPU-sized decoder of the same shape of block: both layer kinds, grouped
# heads and groups of SSM heads, a chunk shorter than a test's sequence
granite_micro = _factory(
    "granite_micro", GraniteConfig, vocab_size=512, hidden_size=64,
    num_hidden_layers=4, layer_types=(MAMBA, MAMBA, ATTENTION, MAMBA),
    num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
    mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8)
