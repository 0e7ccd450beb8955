"""The causal decoder's skeleton, shared by the language families
(``glm_moe_lite.py``, ``mellum.py``): RMSNorm, the bias-free dense layer, the
rotary embedding, ``CausalLM`` (embedding, a family's blocks under
``remat_block``, final norm, untied head, ``return_hidden``) and the registry
factory. A family brings its config (a frozen dataclass of ``config.json``'s
keys with the chip's share beside them), its attention module and its block,
and tells the skeleton two things through the config: ``block(i, dtype,
name)``, layer ``i``'s module, and ``mtp(dtype, name)``, its
multi-token-prediction module or None.

What a block keeps for its backward pass: its input, and the two residuals
of the fused attention core that only the kernel can make, its output and
the per-row logsumexp (``flash_attention.py`` names them ``ATTENTION_OUT``
and ``ATTENTION_LSE``). Everything else in the block is computed again.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ...core.registry import MODELS
from ...ops.pallas.flash_attention import ATTENTION_LSE, ATTENTION_OUT


def _dense(features: int, dtype, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name,
                    kernel_init=nn.initializers.normal(0.02))


# what a block's remat keeps: the fused attention core's two named residuals
KEEP_ATTENTION_CORE = jax.checkpoint_policies.save_only_these_names(
    ATTENTION_OUT, ATTENTION_LSE)


def remat_block(block):
    """A family's block, rematerialised in the backward pass but for the
    attention core's output, O(N D) bytes a layer, and logsumexp, O(N): the
    backward kernels read both, and making them again is the forward kernel
    run a second time, O(N^2 D). Only the fused path
    (``flash_attention.select_path``) names them; on the lax path nothing is
    kept and the block is computed again whole."""
    # flax's lift runs the class it is handed, so the mark that
    # ``forward_kept`` looks for goes on a subclass made before the lift
    marked = type(block.__name__, (block,), {"keeps_attention_core": True})
    return nn.remat(marked, policy=KEEP_ATTENTION_CORE)


def forward_kept(attn: nn.Module, path: str) -> bool:
    """Whether the backward pass of a block's attention module ``attn`` finds
    its core's output and logsumexp kept: the core took the fused ``path``
    and the block is ``remat_block``'s. What the ``kernel`` flight events
    report."""
    return path == "fused" and getattr(attn.parent, "keeps_attention_core",
                                       False)


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(var + self.eps) * scale).astype(self.dtype)


def rope_inv_freq(theta: float, r: int) -> jax.Array:
    """The unscaled rotary frequencies of ``r`` dimensions: theta^(-2i/r)."""
    return theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)


def rotary(x: jax.Array, inv_freq: jax.Array, factor: float = 1.0
           ) -> jax.Array:
    """Rotary embedding over all of the last axis of (..., N, r), positions
    0..N-1, dimension i paired with i + r/2 and turned at ``inv_freq[i]``;
    cos and sin both times ``factor`` (YaRN's attention factor); float32
    inside."""
    n, r = x.shape[-2], x.shape[-1]
    with jax.named_scope("rotary"):     # train/steps.py::STEP_SCOPES
        ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv_freq[None, :]
        cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
        x32 = x.astype(jnp.float32)
        a, b = x32[..., : r // 2], x32[..., r // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                               axis=-1).astype(x.dtype)


class CausalLM(nn.Module):
    """tokens (B, S) int -> float32 logits (B, S, V) of the next token.

    Where the family has a multi-token-prediction module and ``next_tokens``
    (B, S) is given, token i + 1 beside token i, the module runs too and a
    pair comes back (second: logits of token i + 2). ``return_hidden`` hands
    back the normed hidden states before the head, one a head, for a loss
    that never holds the logits whole (``train/language.py``). Every block
    is rematerialised in the backward pass (the config's ``block`` wraps it
    in ``remat_block``)."""
    cfg: Any
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, tokens, train: bool = False,
                 next_tokens: Optional[jax.Array] = None,
                 return_hidden: bool = False):
        c = self.cfg
        embed = nn.Embed(c.vocab_size, c.hidden_size, dtype=self.dtype,
                         embedding_init=nn.initializers.normal(0.02),
                         name="embed")
        head = _dense(c.vocab_size, self.dtype, "head")
        x = embed(tokens)
        for i in range(c.num_hidden_layers):
            x = c.block(i, self.dtype, name=f"layers_{i}")(x)
        hidden = [RMSNorm(c.rms_norm_eps, self.dtype, name="norm")(x)]
        mtp = c.mtp(self.dtype, name="mtp")
        if mtp is not None and next_tokens is None and self.is_initializing():
            next_tokens = tokens
        if mtp is not None and next_tokens is not None:
            hidden.append(mtp(x, embed(next_tokens)))
        if not return_hidden or self.is_initializing():
            logits = [head(h).astype(jnp.float32) for h in hidden]
            if not return_hidden:
                return logits[0] if len(logits) == 1 else tuple(logits)
        return tuple(hidden)


def _factory(name: str, config_cls, **published):
    @MODELS.register(name)
    def build(num_classes: Optional[int] = None, dtype=jnp.bfloat16,
              **overrides):
        """``num_classes`` is the vocabulary this chip holds."""
        cfg = config_cls(**{**published, **overrides})
        if num_classes:
            cfg = dataclasses.replace(cfg, vocab_size=num_classes)
        return CausalLM(cfg, dtype)
    # what the entry is: tools/train.py picks the loader and the loss by it
    build.task = "language"
    build.__name__ = name
    return build
