"""Vision Transformer — the north-star model (BASELINE.md: ViT-B/16 MFU).

Capability surface of classification/vision_transformer/vit_model.py:
drop_path (:12), PatchEmbed (:43), fused-qkv Attention (:71, softmax attn
:100-111), Mlp (:114), Block (:136), VisionTransformer (:164,
forward_features :240 — cls token + learned pos embed), and the model
factories (:290-358: B/16, B/32, L/16, L/32, H/14).

TPU-first design choices (not in the reference):
- bf16 compute / f32 params; logits returned f32.
- on a TPU the attention core runs as two fused Pallas kernels, forward and
  backward (ops/pallas/global_attention.py), wherever its ``select_path``
  says the shape is covered; ``dot_product_attention`` below is the oracle
  and the path for everything else. Attention is still a pluggable callable
  (``attn_fn``): ring and Ulysses attention take that slot.
- ``remat`` wraps each Block with jax.checkpoint (the torch
  gradient-checkpointing analog, swin_transformer.py:410-411) to trade
  FLOPs for HBM.
- token count is static → everything tiles cleanly onto the MXU.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ...core import numerics
from ...core.registry import MODELS
from ...obs import flight
from ...ops.pallas import global_attention as fused_attention


def drop_path(x: jax.Array, rate: float, deterministic: bool,
              rng: Optional[jax.Array] = None) -> jax.Array:
    """Stochastic depth on the residual branch (vit_model.py:12)."""
    if deterministic or rate == 0.0:
        return x
    keep = 1.0 - rate
    shape = (x.shape[0],) + (1,) * (x.ndim - 1)
    mask = jax.random.bernoulli(rng, keep, shape).astype(x.dtype)
    return x / keep * mask


class DropPath(nn.Module):
    rate: float = 0.0

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        if self.rate == 0.0 or deterministic:
            return x
        return drop_path(x, self.rate, deterministic,
                         self.make_rng("dropout"))


class PatchEmbed(nn.Module):
    """Image → patch tokens (vit_model.py:43).

    The reference's strided conv IS a block reshape + matmul; lowering it
    explicitly that way measured +1.2 MFU points on the v5e ViT-B/16 train
    step vs XLA's conv path (July, another runtime; not measured on this
    one). Params keep the conv's HWIO kernel shape
    (p, p, c, embed) and "proj" naming, so checkpoints and torch-weight
    ports are unaffected — the kernel is reshaped at trace time."""
    patch_size: int = 16
    embed_dim: int = 768
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        p = self.patch_size
        b, hh, ww, c = x.shape
        h, w = hh // p, ww // p
        x = x.reshape(b, h, p, w, p, c).transpose(0, 1, 3, 2, 4, 5)
        x = x.reshape(b, h * w, p * p * c)
        return _PatchProj(p, c, self.embed_dim, self.dtype, name="proj")(x)


class _PatchProj(nn.Module):
    """Conv-shaped (HWIO) params applied as a flat matmul (PatchEmbed)."""
    patch_size: int
    in_chans: int
    embed_dim: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        p, c = self.patch_size, self.in_chans
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (p, p, c, self.embed_dim), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros,
                          (self.embed_dim,), jnp.float32)
        y = x.astype(self.dtype) @ kernel.reshape(
            p * p * c, self.embed_dim).astype(self.dtype)
        return y + bias.astype(self.dtype)


def dot_product_attention(q, k, v, dropout_rate=0.0, deterministic=True,
                          rng=None):
    """Naive softmax attention — the lax reference path the Pallas kernels
    (ops/pallas/global_attention.py) are tested against, and the path of
    every shape they do not cover. q,k,v: (B, N, H, D)."""
    scale = q.shape[-1] ** -0.5
    attn = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
    attn = jax.nn.softmax(attn.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_rate > 0 and not deterministic:
        keep = jax.random.bernoulli(rng, 1.0 - dropout_rate, attn.shape)
        attn = attn * keep.astype(attn.dtype) / (1.0 - dropout_rate)
    return jnp.einsum("bhqk,bkhd->bqhd", attn, v)


class Attention(nn.Module):
    """Fused-qkv multi-head attention (vit_model.py:71)."""
    num_heads: int = 8
    qkv_bias: bool = True
    attn_drop: float = 0.0
    proj_drop: float = 0.0
    dtype: Any = jnp.bfloat16
    attn_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        b, n, c = x.shape
        head_dim = c // self.num_heads
        dropout = self.attn_drop > 0 and not deterministic
        path = fused_attention.select_path(
            n, head_dim, dropout=dropout, injected=self.attn_fn is not None,
            initializing=self.is_initializing())
        # one flight event per path and shape, whatever the batch and however
        # often it is traced; ``shape`` is the first one seen
        flight.tally("kernel", ("attention", path, n, self.num_heads,
                                head_dim),
                     member="/".join(self.path), name="attention", path=path,
                     shape=[b, n, self.num_heads, head_dim])
        qkv = nn.Dense(3 * c, use_bias=self.qkv_bias, dtype=self.dtype,
                       name="qkv")(x)
        if path == "fused":
            out = fused_attention.global_attention(qkv, heads=self.num_heads)
        else:
            qkv = qkv.reshape(b, n, 3, self.num_heads, head_dim)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            fn = self.attn_fn or dot_product_attention
            rng = self.make_rng("dropout") if dropout else None
            out = fn(q, k, v, dropout_rate=self.attn_drop,
                     deterministic=deterministic, rng=rng)
            out = out.reshape(b, n, c)
        out = nn.Dense(c, dtype=self.dtype, name="proj")(out)
        out = nn.Dropout(self.proj_drop, deterministic=deterministic)(out)
        return out


class Mlp(nn.Module):
    hidden_ratio: float = 4.0
    drop: float = 0.0
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        c = x.shape[-1]
        x = nn.Dense(int(c * self.hidden_ratio), dtype=self.dtype,
                     name="fc1")(x)
        # GELU via the numerics mode: tanh by default (erf costs 3.8 MFU
        # points on the v5e ViT-B/16 step — core/numerics.py), exact erf
        # under parity mode to match torch nn.GELU() (vit_model.py:114)
        x = numerics.gelu(x)
        x = nn.Dropout(self.drop, deterministic=deterministic)(x)
        x = nn.Dense(c, dtype=self.dtype, name="fc2")(x)
        x = nn.Dropout(self.drop, deterministic=deterministic)(x)
        return x


class Block(nn.Module):
    num_heads: int
    mlp_ratio: float = 4.0
    qkv_bias: bool = True
    drop: float = 0.0
    attn_drop: float = 0.0
    drop_path_rate: float = 0.0
    dtype: Any = jnp.bfloat16
    attn_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        y = nn.LayerNorm(dtype=self.dtype, name="norm1")(x)
        y = Attention(self.num_heads, self.qkv_bias, self.attn_drop,
                      self.drop, self.dtype, self.attn_fn, name="attn")(
            y, deterministic)
        x = x + DropPath(self.drop_path_rate)(y, deterministic)
        y = nn.LayerNorm(dtype=self.dtype, name="norm2")(x)
        y = Mlp(self.mlp_ratio, self.drop, self.dtype, name="mlp")(
            y, deterministic)
        return x + DropPath(self.drop_path_rate)(y, deterministic)


class VisionTransformer(nn.Module):
    img_size: int = 224
    patch_size: int = 16
    num_classes: int = 1000
    embed_dim: int = 768
    depth: int = 12
    num_heads: int = 12
    mlp_ratio: float = 4.0
    qkv_bias: bool = True
    drop_rate: float = 0.0
    attn_drop_rate: float = 0.0
    drop_path_rate: float = 0.0
    representation_size: Optional[int] = None
    dtype: Any = jnp.bfloat16
    remat: bool = False
    attn_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        deterministic = not train
        x = PatchEmbed(self.patch_size, self.embed_dim, self.dtype,
                       name="patch_embed")(x)
        b, n, c = x.shape
        cls = self.param("cls_token", nn.initializers.zeros, (1, 1, c),
                         jnp.float32)
        x = jnp.concatenate(
            [jnp.broadcast_to(cls.astype(x.dtype), (b, 1, c)), x], axis=1)
        pos = self.param("pos_embed",
                         nn.initializers.truncated_normal(0.02),
                         (1, n + 1, c), jnp.float32)
        # explicit broadcast: its transpose is ONE reduce_sum over batch,
        # which GSPMD shards cleanly; the implicit-broadcast add's
        # transpose accumulated pos grads through an add_any chain whose
        # chosen sharding forced an involuntary full rematerialization
        # under data x fsdp meshes (MULTICHIP r3 tail warnings)
        x = x + jnp.broadcast_to(pos.astype(x.dtype), x.shape)
        x = nn.Dropout(self.drop_rate, deterministic=deterministic)(x)

        import numpy as np
        dpr = [float(r) for r in
               np.linspace(0, self.drop_path_rate, self.depth)]
        block_cls = Block
        if self.remat:
            block_cls = nn.remat(Block, static_argnums=(2,))
        for i in range(self.depth):
            x = block_cls(self.num_heads, self.mlp_ratio, self.qkv_bias,
                          self.drop_rate, self.attn_drop_rate, dpr[i],
                          self.dtype, self.attn_fn, name=f"blocks_{i}")(
                x, deterministic)
        x = nn.LayerNorm(dtype=self.dtype, name="norm")(x)
        x = x[:, 0]
        if self.representation_size is not None:
            x = nn.Dense(self.representation_size, dtype=self.dtype,
                         name="pre_logits")(x)
            x = nn.tanh(x)
        # trunc-normal head like the reference (vit_model.py:276-278, ALL
        # Linears std=.01). A zero-init head makes every backbone gradient
        # zero until the head moves — measured as a hard flatline on the
        # 100-class from-scratch runs (runs/convergence/swin_diag_*).
        x = nn.Dense(self.num_classes, dtype=self.dtype, name="head",
                     kernel_init=nn.initializers.truncated_normal(0.01))(x)
        return x.astype(jnp.float32)


def _factory(name, **defaults):
    @MODELS.register(name)
    def build(num_classes: int = 1000, **kw):
        merged = {**defaults, "num_classes": num_classes, **kw}
        return VisionTransformer(**merged)
    build.__name__ = name
    return build


# Factories mirror vit_model.py:290-358 (+ the timm-standard small
# config the reference file derives from, used by the offline
# convergence runs).
vit_small_patch16_224 = _factory("vit_small_patch16_224",
                                 patch_size=16, embed_dim=384, depth=12,
                                 num_heads=6)
# small-image config (56px offline sets: 14x14 tokens); also the
# transformer control for the swin convergence diagnosis (r5)
vit_micro_patch4_56 = _factory("vit_micro_patch4_56",
                               patch_size=4, embed_dim=128, depth=6,
                               num_heads=4, drop_path_rate=0.0)
vit_base_patch16_224 = _factory("vit_base_patch16_224",
                                patch_size=16, embed_dim=768, depth=12,
                                num_heads=12)
vit_base_patch32_224 = _factory("vit_base_patch32_224",
                                patch_size=32, embed_dim=768, depth=12,
                                num_heads=12)
vit_large_patch16_224 = _factory("vit_large_patch16_224",
                                 patch_size=16, embed_dim=1024, depth=24,
                                 num_heads=16)
vit_large_patch32_224 = _factory("vit_large_patch32_224",
                                 patch_size=32, embed_dim=1024, depth=24,
                                 num_heads=16)
vit_huge_patch14_224 = _factory("vit_huge_patch14_224",
                                patch_size=14, embed_dim=1280, depth=32,
                                num_heads=16)
