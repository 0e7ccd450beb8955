"""Plain reference of the Swin Transformer (Liu et al., arXiv:2103.14030):
4x4 patch embedding with norm, stages of pre-norm blocks whose attention runs
inside 7x7 windows (every second block on a grid cyclically shifted by half a
window, with the mask that keeps wrapped-around tokens apart), a learned
relative-position bias per head, 2x2 patch merging between stages, final norm,
mean over tokens, linear head. Stochastic depth on both residual branches,
its rate rising linearly over the blocks. Imports nothing of the program.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from . import ops


def _stages(shapes: dict):
    dim = shapes["hidden_size"]
    res = shapes["image_size"] // shapes["patch_size"]
    for s, (depth, heads) in enumerate(zip(shapes["depths"], shapes["num_heads"])):
        yield s, depth, heads, dim, res
        dim, res = dim * 2, res // 2


def param_spec(shapes: dict) -> dict:
    p, win = shapes["patch_size"], shapes["window_size"]
    spec = {"patch_embed": {"kernel": ((p, p, 3, shapes["hidden_size"]), "normal"),
                            "bias": ((shapes["hidden_size"],), "zeros")},
            "patch_norm": ops.norm_spec(shapes["hidden_size"])}
    last = len(shapes["depths"]) - 1
    final = shapes["hidden_size"]
    for s, depth, heads, dim, res in _stages(shapes):
        hidden = int(dim * shapes["mlp_ratio"])
        for i in range(depth):
            spec[f"stage{s}_block{i}"] = {
                "norm1": ops.norm_spec(dim),
                "attn": {"qkv": ops.dense_spec(dim, 3 * dim),
                         "relative_position_bias_table":
                             (((2 * min(win, res) - 1) ** 2, heads), "normal"),
                         "proj": ops.dense_spec(dim, dim)},
                "norm2": ops.norm_spec(dim),
                "mlp": {"fc1": ops.dense_spec(dim, hidden),
                        "fc2": ops.dense_spec(hidden, dim)},
            }
        if s < last:
            spec[f"stage{s}_merge"] = {
                "norm": ops.norm_spec(4 * dim),
                "reduction": ops.dense_spec(4 * dim, 2 * dim, bias=False)}
            final = 2 * dim
    spec["norm"] = ops.norm_spec(final)
    spec["head"] = ops.dense_spec(final, shapes["num_classes"])
    return spec


def droppath_sites(shapes: dict) -> list:
    """(module path, rate) of every stochastic-depth draw, in forward order.
    A branch whose rate is 0 draws nothing."""
    total = sum(shapes["depths"])
    rates = np.linspace(0.0, shapes["drop_path_rate"], total)
    sites, k = [], 0
    for s, depth, *_ in _stages(shapes):
        for i in range(depth):
            if rates[k] > 0:
                for branch in (0, 1):
                    sites.append(((f"stage{s}_block{i}", f"DropPath_{branch}"),
                                  float(rates[k])))
            k += 1
    return sites


def _partition(x, win: int):
    b, h, w, c = x.shape
    x = x.reshape(b, h // win, win, w // win, win, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, (h // win) * (w // win), win * win, c)


def _merge_windows(x, win: int, h: int, w: int):
    b, c = x.shape[0], x.shape[-1]
    x = x.reshape(b, h // win, w // win, win, win, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, h, w, c)


def _shift_mask(h: int, w: int, win: int, shift: int) -> np.ndarray:
    """(windows, N, N): 0 where two tokens of a shifted window came from the
    same region of the image, a large negative number where not."""
    region = np.zeros((h, w), np.float32)
    cuts = (slice(0, -win), slice(-win, -shift), slice(-shift, None))
    for a, hs in enumerate(cuts):
        for b, ws in enumerate(cuts):
            region[hs, ws] = 3 * a + b
    wins = region.reshape(h // win, win, w // win, win).transpose(0, 2, 1, 3)
    wins = wins.reshape(-1, win * win)
    return np.where(wins[:, None, :] != wins[:, :, None], -1e9, 0.0).astype(
        np.float32)


def _relative_index(win: int) -> np.ndarray:
    """(N, N) row of the bias table for each pair of window positions."""
    ys, xs = np.meshgrid(np.arange(win), np.arange(win), indexing="ij")
    ys, xs = ys.reshape(-1), xs.reshape(-1)
    dy = ys[:, None] - ys[None, :] + win - 1
    dx = xs[:, None] - xs[None, :] + win - 1
    return dy * (2 * win - 1) + dx


def _block(x, blk, res: int, heads: int, win: int, shift: int, mode: str,
           keep_attn, keep_mlp):
    b, n, c = x.shape
    win = min(win, res)
    shift = 0 if win >= res else shift
    y = ops.layer_norm(x, blk["norm1"]).reshape(b, res, res, c)
    if shift:
        y = jnp.roll(y, (-shift, -shift), axis=(1, 2))
    y = _partition(y, win)                               # (b, nW, N, c)
    nw, tokens = y.shape[1], y.shape[2]
    qkv = ops.dense(y, blk["attn"]["qkv"], mode)
    qkv = qkv.reshape(b, nw, tokens, 3, heads, c // heads)
    table = blk["attn"]["relative_position_bias_table"]
    bias = table[_relative_index(win).reshape(-1)].reshape(tokens, tokens, heads)
    bias = bias.transpose(2, 0, 1)[None, None]           # (1, 1, heads, N, N)
    if shift:
        bias = bias + jnp.asarray(_shift_mask(res, res, win, shift))[None, :, None]
    y = ops.attention(qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2], mode,
                      bias=bias)
    y = ops.dense(y.reshape(b, nw, tokens, c), blk["attn"]["proj"], mode)
    y = _merge_windows(y, win, res, res)
    if shift:
        y = jnp.roll(y, (shift, shift), axis=(1, 2))
    y = y.reshape(b, n, c)
    x = x + (y if keep_attn is None else y * keep_attn[:, None, None])
    y = ops.mlp(ops.layer_norm(x, blk["norm2"]), blk["mlp"], mode)
    return x + (y if keep_mlp is None else y * keep_mlp[:, None, None])


def forward(params, images, shapes: dict, mode: str, keep=None):
    """images (b, H, W, 3) float32 -> logits (b, classes) float32. ``keep``
    is (sites, b): each stochastic-depth branch's factor per row, 0 or
    1/(1 - rate), in the order of ``droppath_sites``; None leaves all on."""
    p, win = shapes["patch_size"], shapes["window_size"]
    b, hh, ww, c = images.shape
    h, w = hh // p, ww // p
    x = images.reshape(b, h, p, w, p, c).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(b, h * w, p * p * c)
    emb = params["patch_embed"]
    x = ops.einsum("bni,io->bno", x, emb["kernel"].reshape(p * p * c, -1),
                   mode) + emb["bias"]
    x = ops.layer_norm(x, params["patch_norm"])
    sites = [path for path, _ in droppath_sites(shapes)]
    last = len(shapes["depths"]) - 1
    for s, depth, heads, dim, res in _stages(shapes):
        for i in range(depth):
            name = f"stage{s}_block{i}"
            factors = [None, None]
            if keep is not None:
                for branch in (0, 1):
                    site = (name, f"DropPath_{branch}")
                    if site in sites:
                        factors[branch] = keep[sites.index(site)]
            x = _block(x, params[name], res, heads, win,
                       0 if i % 2 == 0 else win // 2, mode, *factors)
        if s < last:
            m = params[f"stage{s}_merge"]
            x = x.reshape(b, res // 2, 2, res // 2, 2, dim)
            x = x.transpose(0, 1, 3, 4, 2, 5).reshape(b, (res // 2) ** 2, 4 * dim)
            x = ops.dense(ops.layer_norm(x, m["norm"]), m["reduction"], mode)
    x = jnp.mean(ops.layer_norm(x, params["norm"]), axis=1)
    return ops.dense(x, params["head"], mode)
