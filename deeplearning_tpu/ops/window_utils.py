"""Window partition/merge + shifted-window masks + relative position index.

Pure-lax reference implementations of Swin's window machinery
(classification/swin_transformer/models/swin_transformer.py: window_partition
:25, window_reverse :40, the shift mask construction :233-238, and the
relative-position-bias index :70-166). ``windowed_attention_reference`` is
the golden path the fused Pallas kernels (ops/pallas/window_attention.py) are
tested against, forward and backward — the role unit_test.py played for the
reference's CUDA kernel — and the path v2 attention and a CPU backend run
(``window_attention.select_path``).

XLA note: roll, partition and merge are reshapes and transposes that the TPU
compiler turns into layout copies, not into the neighbouring matmul's
epilogue. On the chip it keeps the partitioned windows token-major,
``(N, BW, C)`` physically, and the fused kernels read and write that order
(PR 31), so the copies that are left are the partition's, the merge's and the
roll's own, forward and backward; what the kernels remove is the per-window
score matrix's trips through HBM and every copy at their own boundary.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def window_partition(x: jax.Array, window: int) -> jax.Array:
    """(B, H, W, C) -> (B*nW, window*window, C)."""
    b, h, w, c = x.shape
    x = x.reshape(b, h // window, window, w // window, window, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(-1, window * window, c)


def window_merge(windows: jax.Array, window: int, h: int, w: int) -> jax.Array:
    """(B*nW, window*window, C) -> (B, H, W, C)."""
    c = windows.shape[-1]
    b = windows.shape[0] // ((h // window) * (w // window))
    x = windows.reshape(b, h // window, w // window, window, window, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, h, w, c)


def shift_window_mask(h: int, w: int, window: int, shift: int) -> np.ndarray:
    """Additive attention mask (nW, N, N) with 0 / -inf for shifted windows
    (swin_transformer.py:233-238 construction, computed host-side once)."""
    img = np.zeros((1, h, w, 1), np.float32)
    cnt = 0
    for hs in (slice(0, -window), slice(-window, -shift), slice(-shift, None)):
        for ws in (slice(0, -window), slice(-window, -shift),
                   slice(-shift, None)):
            img[:, hs, ws, :] = cnt
            cnt += 1
    # region ids are already laid out in the shifted frame — partition
    # directly, no roll (matches the reference construction). Pure numpy so
    # it stays host-side even when called during a jit trace.
    wins = img.reshape(1, h // window, window, w // window, window, 1)
    wins = wins.transpose(0, 1, 3, 2, 4, 5).reshape(-1, window * window)
    diff = wins[:, None, :] - wins[:, :, None]
    return np.where(diff != 0, -1e9, 0.0).astype(np.float32)


def relative_position_index(window: int) -> np.ndarray:
    """(N, N) index into the (2w-1)^2 relative-position-bias table
    (swin_transformer.py:82-96 arithmetic, host-side)."""
    coords = np.stack(np.meshgrid(np.arange(window), np.arange(window),
                                  indexing="ij"))           # (2, w, w)
    flat = coords.reshape(2, -1)
    rel = flat[:, :, None] - flat[:, None, :]                # (2, N, N)
    rel = rel.transpose(1, 2, 0).astype(np.int64)
    rel[:, :, 0] += window - 1
    rel[:, :, 1] += window - 1
    rel[:, :, 0] *= 2 * window - 1
    return (rel[:, :, 0] + rel[:, :, 1]).astype(np.int32)    # (N, N)


def windowed_attention_reference(
    qkv: jax.Array,            # (BW, N, 3, heads, d)
    bias: jax.Array,           # (heads, N, N) relative-position bias
    mask: Optional[jax.Array], # (nW, N, N) shift mask or None
) -> jax.Array:
    """Naive per-window attention — numerical golden path: materialises the
    float32 scores ``(BW, heads, N, N)``, and autodiff saves the softmax for
    the backward. Returns (BW, N, heads*d)."""
    bw, n, _, heads, d = qkv.shape
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]   # (BW, N, heads, d)
    scale = d ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k).astype(jnp.float32)
    s = s + bias[None].astype(jnp.float32)
    if mask is not None:
        nw = mask.shape[0]
        s = s.reshape(bw // nw, nw, heads, n, n) + \
            mask[None, :, None].astype(jnp.float32)
        s = s.reshape(bw, heads, n, n)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return out.reshape(bw, n, heads * d)
