"""Attention adapters for the models' ``attn_fn`` slot.

``get_attn_fn("flash")`` plugs into models' ``attn_fn`` slot
(models/classification/vit.py Attention). The naive path is the golden
reference. None of these adapters is a production path: on a TPU the
ViT attention core runs the fused kernels of
``ops/pallas/global_attention.py`` (chosen by its ``select_path``; an
injected ``attn_fn`` turns them off), and in the ViT-B/16 training cell
the head-batched flash kernel and ``jax.nn.dot_product_attention`` both
lose to the naive path (steps of 189.4 and 173.3 ms against 144.8;
PERF.md, PR 29). They stay for the ring (``use_flash``) until ROADMAP D3
deletes them. Attention dropout is applied on the naive path only; the
adapters refuse it (attn-dropout is 0 in all reference training configs;
ViT uses drop_path instead).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax

from .pallas.flash_attention import flash_attention, flash_attention_hb


def _check_no_dropout(dropout_rate: float, deterministic: bool):
    if dropout_rate > 0.0 and not deterministic:
        raise NotImplementedError(
            "flash attention does not implement attention dropout; set "
            "attn_drop_rate=0 (use drop_path for regularization) or use "
            "the naive attention path.")


def flash_attn_adapter(q, k, v, dropout_rate: float = 0.0,
                       deterministic: bool = True,
                       rng: Optional[jax.Array] = None):
    """(B, N, H, D) adapter matching models' attn_fn signature (per-head
    kernel — the long-N path)."""
    _check_no_dropout(dropout_rate, deterministic)
    del rng
    t = lambda x: x.transpose(0, 2, 1, 3)
    return t(flash_attention(t(q), t(k), t(v)))


def flash_hb_adapter(q, k, v, dropout_rate: float = 0.0,
                     deterministic: bool = True,
                     rng: Optional[jax.Array] = None):
    """(B, N, H, D) adapter for the head-batched kernel — the short-N
    path (ViT/MAE token counts), trainable."""
    _check_no_dropout(dropout_rate, deterministic)
    del rng
    t = lambda x: x.transpose(0, 2, 1, 3)
    return t(flash_attention_hb(t(q), t(k), t(v)))


def sdpa_adapter(q, k, v, dropout_rate: float = 0.0,
                 deterministic: bool = True,
                 rng: Optional[jax.Array] = None):
    """(B, N, H, D) adapter over jax.nn.dot_product_attention — the
    XLA-native SDPA entry (can lower to a fused attention)."""
    _check_no_dropout(dropout_rate, deterministic)
    del rng
    return jax.nn.dot_product_attention(q, k, v)


def get_attn_fn(name: str = "flash") -> Optional[Callable]:
    if name in ("flash", "pallas"):
        return flash_attn_adapter
    if name in ("flash_hb", "pallas_hb", "head_batched"):
        return flash_hb_adapter
    if name in ("sdpa", "xla"):
        return sdpa_adapter
    if name in ("naive", "lax", "reference"):
        return None  # models fall back to their built-in naive path
    raise ValueError(f"Unknown attention implementation {name!r}")
