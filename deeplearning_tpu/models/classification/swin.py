"""Swin Transformer v1/v2 — hierarchical windowed attention.

Capability surface of classification/swin_transformer/models/
swin_transformer.py: WindowAttention with relative position bias (:70),
SwinTransformerBlock with cyclic shift + mask (:168), PatchMerging (:308),
BasicLayer, SwinTransformer (:410-411 gradient checkpointing), and the
v2 variants (swin_transformer_v2.py: cosine attention with learned
logit scale, log-spaced continuous position bias MLP).

TPU-first: v1 window attention runs the fused Pallas kernels
(ops/pallas/window_attention.py: scores, bias, mask, softmax and their
backward stay in VMEM) wherever they compile; v2 cosine attention, a CPU
backend and the one eager pass of ``model.init`` run the lax path.
``window_attention.select_path`` makes that choice from what the layer can
see; there is no flag. Roll, partition and merge are lax ops: XLA turns them
into layout copies of their own, and keeps the windows token-major between
them, which is the order the kernels read the qkv rows in and write their
output in: nothing is copied at the kernels' boundary. NHWC throughout.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ...core.registry import MODELS
from ...obs import flight
from ...ops import window_utils as wu
from ...ops.pallas import window_attention as fused_attention
from .vit import DropPath, Mlp


class WindowAttention(nn.Module):
    """Window MHSA with relative position bias (v1) or cosine attention
    with log-CPB (v2)."""
    dim: int
    window: int
    num_heads: int
    qkv_bias: bool = True
    v2: bool = False
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, mask: Optional[jax.Array] = None,
                 deterministic: bool = True):
        bw, n, c = x.shape
        d = c // self.num_heads
        path = fused_attention.select_path(self.v2, self.is_initializing())
        masked = mask is not None
        # one flight event per path and per-window shape, whatever the batch
        # and however often it is traced; ``shape`` is the first one seen.
        # ``interface``: the order of the rows the kernels read and write
        flight.tally("kernel", ("window_attention", path, n, c,
                                self.num_heads, masked),
                     member="/".join(self.path), name="window_attention",
                     path=path, interface=fused_attention.interface(path),
                     shape=[bw, n, self.num_heads, d], masked=masked)
        if self.v2 and self.qkv_bias:
            # v2 uses q/v biases only: a k bias is NOT softmax-invariant
            # under cosine attention (it shifts keys before normalization).
            qkv = nn.Dense(3 * c, use_bias=False, dtype=self.dtype,
                           name="qkv")(x)
            q_bias = self.param("q_bias", nn.initializers.zeros, (c,),
                                jnp.float32)
            v_bias = self.param("v_bias", nn.initializers.zeros, (c,),
                                jnp.float32)
            bias_vec = jnp.concatenate(
                [q_bias, jnp.zeros_like(q_bias), v_bias])
            qkv = qkv + bias_vec.astype(qkv.dtype)
        else:
            qkv = nn.Dense(3 * c, use_bias=self.qkv_bias, dtype=self.dtype,
                           name="qkv")(x)

        if self.v2:
            qkv = qkv.reshape(bw, n, 3, self.num_heads, d)
            # swin v2: cosine attention + continuous position bias MLP over
            # log-spaced coords (swin_transformer_v2.py surface).
            logit_scale = self.param(
                "logit_scale",
                lambda key, shape: jnp.log(10.0) * jnp.ones(shape),
                (self.num_heads, 1, 1))
            rel_coords = wu.relative_position_index(self.window)
            coords_table = self._log_coords_table()
            cpb = nn.Sequential([
                nn.Dense(512, dtype=jnp.float32, name="cpb_fc1"),
                nn.relu,
                nn.Dense(self.num_heads, use_bias=False, dtype=jnp.float32,
                         name="cpb_fc2")])(coords_table)
            bias = 16.0 * nn.sigmoid(cpb[rel_coords.reshape(-1)])
            bias = bias.reshape(n, n, self.num_heads).transpose(2, 0, 1)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            from ...ops.losses import safe_normalize
            qn = safe_normalize(q.astype(jnp.float32), axis=-1)
            kn = safe_normalize(k.astype(jnp.float32), axis=-1)
            scale = jnp.exp(jnp.minimum(logit_scale, jnp.log(100.0)))
            s = jnp.einsum("bqhd,bkhd->bhqk", qn, kn).astype(jnp.float32)
            s = s * scale[None] + bias[None]
            if mask is not None:
                nw = mask.shape[0]
                s = s.reshape(bw // nw, nw, self.num_heads, n, n) \
                    + mask[None, :, None]
                s = s.reshape(bw, self.num_heads, n, n)
            p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
            out = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(bw, n, c)
        else:
            table = self.param(
                "relative_position_bias_table",
                nn.initializers.truncated_normal(0.02),
                ((2 * self.window - 1) ** 2, self.num_heads), jnp.float32)
            idx = wu.relative_position_index(self.window)
            bias = table[idx.reshape(-1)].reshape(n, n, self.num_heads)
            bias = bias.transpose(2, 0, 1)          # (heads, N, N)
            if path == "fused":
                out = fused_attention.window_attention(
                    qkv, bias, mask, heads=self.num_heads)
            else:
                out = wu.windowed_attention_reference(
                    qkv.reshape(bw, n, 3, self.num_heads, d), bias, mask)

        out = nn.Dense(c, dtype=self.dtype, name="proj")(out)
        return out

    def _log_coords_table(self):
        w = self.window
        rel = np.arange(-(w - 1), w, dtype=np.float32)
        table = np.stack(np.meshgrid(rel, rel, indexing="ij"),
                         axis=-1).reshape(-1, 2)
        table = table / (w - 1) * 8
        table = np.sign(table) * np.log2(np.abs(table) + 1.0) / np.log2(8)
        return jnp.asarray(table)


class SwinBlock(nn.Module):
    dim: int
    input_resolution: Tuple[int, int]
    num_heads: int
    window: int = 7
    shift: int = 0
    mlp_ratio: float = 4.0
    qkv_bias: bool = True
    drop: float = 0.0
    drop_path_rate: float = 0.0
    v2: bool = False
    dtype: Any = jnp.bfloat16
    moe: bool = False                 # MoE MLP (swin_transformer_moe)
    num_experts: int = 8

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        h, w = self.input_resolution
        b, n, c = x.shape
        window = min(self.window, h, w)
        shift = 0 if window >= min(h, w) else self.shift

        shortcut = x
        if not self.v2:                      # v1: pre-norm
            x = nn.LayerNorm(dtype=self.dtype, name="norm1")(x)
        x = x.reshape(b, h, w, c)
        if shift > 0:
            x = jnp.roll(x, (-shift, -shift), axis=(1, 2))
            mask = jnp.asarray(wu.shift_window_mask(h, w, window, shift))
        else:
            mask = None
        wins = wu.window_partition(x, window)          # (B*nW, win², C)
        wins = WindowAttention(self.dim, window, self.num_heads,
                               self.qkv_bias, self.v2, self.dtype,
                               name="attn")(
            wins, mask, deterministic)
        x = wu.window_merge(wins, window, h, w)
        if shift > 0:
            x = jnp.roll(x, (shift, shift), axis=(1, 2))
        x = x.reshape(b, n, c)
        if self.v2:                          # v2: post-norm (res-post-norm)
            x = nn.LayerNorm(dtype=self.dtype, name="norm1")(x)
        x = shortcut + DropPath(self.drop_path_rate)(x, deterministic)

        y = x
        if not self.v2:
            y = nn.LayerNorm(dtype=self.dtype, name="norm2")(y)
        if self.moe:
            from ...parallel.moe import MoEMlp
            y, aux = MoEMlp(self.num_experts,
                            hidden_ratio=self.mlp_ratio,
                            drop=self.drop,
                            dtype=self.dtype, name="moe_mlp")(
                y, deterministic)
            self.sow("losses", "moe_aux", aux)
        else:
            y = Mlp(self.mlp_ratio, self.drop, self.dtype, name="mlp")(
                y, deterministic)
        if self.v2:
            y = nn.LayerNorm(dtype=self.dtype, name="norm2")(y)
        return x + DropPath(self.drop_path_rate)(y, deterministic)


class SwinMLPBlock(nn.Module):
    """Swin-MLP block (swin_mlp.py:59-156): window attention replaced by a
    grouped token-mixing linear map — per head, a learned (win², win²)
    matrix over window positions (the reference's grouped Conv1d over
    nH·win² channels). Shifted blocks zero-pad by (window−shift, shift)
    on each spatial side and crop back, instead of cyclic roll + mask.

    TPU-first: the token mix is one batched einsum over
    (windows × heads) — an MXU matmul, no conv needed.
    """
    dim: int
    input_resolution: Tuple[int, int]
    num_heads: int
    window: int = 7
    shift: int = 0
    mlp_ratio: float = 4.0
    drop: float = 0.0
    drop_path_rate: float = 0.0
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        h, w = self.input_resolution
        b, n, c = x.shape
        window = min(self.window, h, w)
        shift = 0 if window >= min(h, w) else self.shift
        d = c // self.num_heads
        n_win = window * window

        shortcut = x
        x = nn.LayerNorm(dtype=self.dtype, name="norm1")(x)
        x = x.reshape(b, h, w, c)
        if shift > 0:
            # P_l = P_t = window - shift, P_r = P_b = shift (swin_mlp.py:91)
            pt, pb = window - shift, shift
            x = jnp.pad(x, ((0, 0), (pt, pb), (pt, pb), (0, 0)))
        hh, ww = x.shape[1], x.shape[2]
        wins = wu.window_partition(x, window)          # (B·nW, win², C)
        nwb = wins.shape[0]
        wins = wins.reshape(nwb, n_win, self.num_heads, d)
        kernel = self.param(
            "spatial_mlp_kernel", nn.initializers.lecun_normal(),
            (self.num_heads, n_win, n_win), jnp.float32)
        bias = self.param("spatial_mlp_bias", nn.initializers.zeros,
                          (self.num_heads, n_win), jnp.float32)
        wins = jnp.einsum("nihd,hoi->nohd", wins,
                          kernel.astype(wins.dtype)) \
            + bias.T[None, :, :, None].astype(wins.dtype)
        wins = wins.reshape(nwb, n_win, c)
        x = wu.window_merge(wins, window, hh, ww)
        if shift > 0:
            x = x[:, pt:pt + h, pt:pt + w, :]
        x = x.reshape(b, n, c)
        x = shortcut + DropPath(self.drop_path_rate)(x, deterministic)

        y = nn.LayerNorm(dtype=self.dtype, name="norm2")(x)
        y = Mlp(self.mlp_ratio, self.drop, self.dtype, name="mlp")(
            y, deterministic)
        return x + DropPath(self.drop_path_rate)(y, deterministic)


class PatchMerging(nn.Module):
    """2×2 patch merge + channel double (swin_transformer.py:308). v2 moves
    the norm AFTER the reduction (res-post-norm, over 2C not 4C)."""
    input_resolution: Tuple[int, int]
    dtype: Any = jnp.bfloat16
    v2: bool = False

    @nn.compact
    def __call__(self, x):
        h, w = self.input_resolution
        b, n, c = x.shape
        # channel order matches the reference concat [x0;x1;x2;x3] =
        # [(0,0),(1,0),(0,1),(1,1)] over (h-sub, w-sub), so pretrained
        # reduction/norm weights load without a channel permutation
        x = x.reshape(b, h // 2, 2, w // 2, 2, c)
        x = x.transpose(0, 1, 3, 4, 2, 5).reshape(b, (h // 2) * (w // 2),
                                                  4 * c)
        if self.v2:
            x = nn.Dense(2 * c, use_bias=False, dtype=self.dtype,
                         name="reduction")(x)
            return nn.LayerNorm(dtype=self.dtype, name="norm")(x)
        x = nn.LayerNorm(dtype=self.dtype, name="norm")(x)
        return nn.Dense(2 * c, use_bias=False, dtype=self.dtype,
                        name="reduction")(x)


class SwinTransformer(nn.Module):
    # input-shape driven: resolution comes from the actual input (H, W);
    # factory names carry the nominal train resolution only
    patch_size: int = 4
    num_classes: int = 1000
    embed_dim: int = 96
    depths: Sequence[int] = (2, 2, 6, 2)
    num_heads: Sequence[int] = (3, 6, 12, 24)
    window: int = 7
    mlp_ratio: float = 4.0
    qkv_bias: bool = True
    drop_rate: float = 0.0
    drop_path_rate: float = 0.1
    v2: bool = False
    dtype: Any = jnp.bfloat16
    remat: bool = False
    moe: bool = False                 # MoE MLP in every 2nd block
    num_experts: int = 8
    spatial_mlp: bool = False         # Swin-MLP (swin_mlp.py) blocks
    ape: bool = False                 # absolute position embedding
    # (swin_transformer.py:516-533). Swin's only position signal is the
    # window-RELATIVE bias + merging hierarchy; tasks whose label depends
    # on absolute layout (e.g. the ordered digit-pair hard set, where
    # ResNet learns via conv zero-padding leakage but swin flatlines —
    # runs/convergence/swin_diag_*) need this on.

    @nn.compact
    def __call__(self, x, train: bool = False):
        deterministic = not train
        x = x.astype(self.dtype)
        x = nn.Conv(self.embed_dim, (self.patch_size, self.patch_size),
                    strides=(self.patch_size, self.patch_size),
                    dtype=self.dtype, name="patch_embed")(x)
        b, h, w, c = x.shape
        x = x.reshape(b, h * w, c)
        x = nn.LayerNorm(dtype=self.dtype, name="patch_norm")(x)
        if self.ape:
            pos = self.param("absolute_pos_embed",
                             nn.initializers.truncated_normal(0.02),
                             (1, h * w, c), jnp.float32)
            x = x + pos.astype(self.dtype)
        x = nn.Dropout(self.drop_rate, deterministic=deterministic)(x)

        total_depth = sum(self.depths)
        dpr = np.linspace(0, self.drop_path_rate, total_depth)
        block_idx = 0
        res = (h, w)
        dim = self.embed_dim
        for stage, (depth, heads) in enumerate(zip(self.depths,
                                                   self.num_heads)):
            for i in range(depth):
                shift = 0 if i % 2 == 0 else self.window // 2
                if self.spatial_mlp:
                    blk = SwinMLPBlock
                    if self.remat:
                        blk = nn.remat(SwinMLPBlock, static_argnums=(2,))
                    x = blk(dim, res, heads, self.window, shift,
                            self.mlp_ratio, self.drop_rate,
                            float(dpr[block_idx]), self.dtype,
                            name=f"stage{stage}_block{i}")(x, deterministic)
                else:
                    blk = SwinBlock
                    if self.remat:
                        blk = nn.remat(SwinBlock, static_argnums=(2,))
                    x = blk(dim, res, heads, self.window, shift,
                            self.mlp_ratio, self.qkv_bias, self.drop_rate,
                            float(dpr[block_idx]), self.v2, self.dtype,
                            self.moe and i % 2 == 1, self.num_experts,
                            name=f"stage{stage}_block{i}")(x, deterministic)
                block_idx += 1
            if stage < len(self.depths) - 1:
                x = PatchMerging(res, self.dtype, self.v2,
                                 name=f"stage{stage}_merge")(x)
                res = (res[0] // 2, res[1] // 2)
                dim *= 2
        x = nn.LayerNorm(dtype=self.dtype, name="norm")(x)
        x = jnp.mean(x, axis=1)
        # trunc-normal head like the reference (swin_transformer.py:564-566,
        # ALL Linears std=.02). Zero-init left logits identically zero at
        # init, so backbone grads were zero until the head moved — the
        # 100-class flatline root cause (runs/convergence/swin_diag_*).
        x = nn.Dense(self.num_classes, dtype=self.dtype, name="head",
                     kernel_init=nn.initializers.truncated_normal(0.02))(x)
        return x.astype(jnp.float32)


def _factory(name, **defaults):
    @MODELS.register(name)
    def build(num_classes: int = 1000, **kw):
        return SwinTransformer(**{**defaults, "num_classes": num_classes,
                                  **kw})
    build.__name__ = name
    return build


swin_tiny_patch4_window7_224 = _factory(
    "swin_tiny_patch4_window7_224", embed_dim=96, depths=(2, 2, 6, 2),
    num_heads=(3, 6, 12, 24))
swin_small_patch4_window7_224 = _factory(
    "swin_small_patch4_window7_224", embed_dim=96, depths=(2, 2, 18, 2),
    num_heads=(3, 6, 12, 24))
swin_base_patch4_window7_224 = _factory(
    "swin_base_patch4_window7_224", embed_dim=128, depths=(2, 2, 18, 2),
    num_heads=(4, 8, 16, 32))
swin_large_patch4_window7_224 = _factory(
    "swin_large_patch4_window7_224", embed_dim=192, depths=(2, 2, 18, 2),
    num_heads=(6, 12, 24, 48))
swinv2_tiny_patch4_window7_224 = _factory(
    "swinv2_tiny_patch4_window7_224", embed_dim=96, depths=(2, 2, 6, 2),
    num_heads=(3, 6, 12, 24), v2=True)
swinv2_base_patch4_window7_224 = _factory(
    "swinv2_base_patch4_window7_224", embed_dim=128, depths=(2, 2, 18, 2),
    num_heads=(4, 8, 16, 32), v2=True)
# MoE variant (swin_transformer_moe.py surface): MoE MLP in alternating
# blocks; aux losses are sow'n under the "losses" collection
swin_moe_tiny_patch4_window7_224 = _factory(
    "swin_moe_tiny_patch4_window7_224", embed_dim=96, depths=(2, 2, 6, 2),
    num_heads=(3, 6, 12, 24), moe=True)
# small-image MoE config for the offline convergence runs (56px digits):
# patch 2 / 28->14 token grid keeps the 7-window shifted path + merges
swin_moe_micro_patch2_window7 = _factory(
    "swin_moe_micro_patch2_window7", patch_size=2, embed_dim=32,
    depths=(2, 2), num_heads=(2, 4), moe=True, num_experts=4,
    drop_path_rate=0.0)
# dense twin of the micro MoE config — the equal-size baseline for MoE
# convergence A/B runs (VERDICT r4 #3)
swin_micro_patch2_window7 = _factory(
    "swin_micro_patch2_window7", patch_size=2, embed_dim=32,
    depths=(2, 2), num_heads=(2, 4), drop_path_rate=0.0)
# 3-stage 56px configs (28->14->7 token grids): the micro 2-stage/dim-32
# pair flatlines on the 100-class hard set at every LR/schedule tested
# (r5 diag matrix, runs/convergence/swin_diag_*) while ResNet-18 reaches
# 0.9 — capacity, not optimization; these are the smallest swin shapes
# that actually learn the set
swin_mini_patch2_window7 = _factory(
    "swin_mini_patch2_window7", patch_size=2, embed_dim=64,
    depths=(2, 2, 4), num_heads=(2, 4, 8), drop_path_rate=0.0)
swin_moe_mini_patch2_window7 = _factory(
    "swin_moe_mini_patch2_window7", patch_size=2, embed_dim=64,
    depths=(2, 2, 4), num_heads=(2, 4, 8), moe=True, num_experts=4,
    drop_path_rate=0.0)
# +APE twins: the ordered-pair task is position-dependent (see the ape
# field comment); these are the configs that learn it
swin_mini_patch2_window7_ape = _factory(
    "swin_mini_patch2_window7_ape", patch_size=2, embed_dim=64,
    depths=(2, 2, 4), num_heads=(2, 4, 8), drop_path_rate=0.0, ape=True)
swin_moe_mini_patch2_window7_ape = _factory(
    "swin_moe_mini_patch2_window7_ape", patch_size=2, embed_dim=64,
    depths=(2, 2, 4), num_heads=(2, 4, 8), moe=True, num_experts=4,
    drop_path_rate=0.0, ape=True)
# Swin-MLP variants (swin_mlp.py; configs/swin_mlp_*.yaml): cN = head dim,
# heads per stage = stage dim / N
swin_mlp_tiny_c24_patch4_window8_256 = _factory(
    "swin_mlp_tiny_c24_patch4_window8_256", embed_dim=96,
    depths=(2, 2, 6, 2), num_heads=(4, 8, 16, 32), window=8,
    spatial_mlp=True)
swin_mlp_base_patch4_window7_224 = _factory(
    "swin_mlp_base_patch4_window7_224", embed_dim=128,
    depths=(2, 2, 18, 2), num_heads=(4, 8, 16, 32), window=7,
    spatial_mlp=True)
