"""Plain reference of the Vision Transformer (Dosovitskiy et al.,
arXiv:2010.11929): patch embedding, class token, learned position embedding,
pre-norm blocks (multi-head attention, MLP with GELU), final norm, linear head
on the class token. Shapes come from the configuration's file; parameter names
are the usual ones (``blocks_<i>/attn/qkv/kernel`` ...), so the same tree can
be handed to the program. Imports nothing of the program.
"""

from __future__ import annotations

import jax.numpy as jnp

from . import ops


def param_spec(shapes: dict) -> dict:
    d, p = shapes["hidden_size"], shapes["patch_size"]
    tokens = (shapes["image_size"] // p) ** 2 + 1
    hidden = int(d * shapes["mlp_ratio"])
    spec = {
        "patch_embed": {"proj": {"kernel": ((p, p, 3, d), "normal"),
                                 "bias": ((d,), "zeros")}},
        "cls_token": ((1, 1, d), "normal"),
        "pos_embed": ((1, tokens, d), "normal"),
        "norm": ops.norm_spec(d),
        "head": ops.dense_spec(d, shapes["num_classes"]),
    }
    for i in range(shapes["num_layers"]):
        spec[f"blocks_{i}"] = {
            "norm1": ops.norm_spec(d),
            "attn": {"qkv": ops.dense_spec(d, 3 * d),
                     "proj": ops.dense_spec(d, d)},
            "norm2": ops.norm_spec(d),
            "mlp": {"fc1": ops.dense_spec(d, hidden),
                    "fc2": ops.dense_spec(hidden, d)},
        }
    return spec


def droppath_sites(shapes: dict) -> list:
    """(module path, rate) of every stochastic-depth draw, in order: none."""
    return []


def forward(params, images, shapes: dict, mode: str, keep=None):
    """images (b, H, W, 3) float32 -> logits (b, classes) float32."""
    p, d, heads = shapes["patch_size"], shapes["hidden_size"], shapes["num_heads"]
    b, hh, ww, c = images.shape
    h, w = hh // p, ww // p
    x = images.reshape(b, h, p, w, p, c).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(b, h * w, p * p * c)
    proj = params["patch_embed"]["proj"]
    x = ops.einsum("bni,io->bno", x, proj["kernel"].reshape(p * p * c, d),
                   mode) + proj["bias"]
    cls = jnp.broadcast_to(params["cls_token"], (b, 1, d))
    x = jnp.concatenate([cls, x], axis=1) + params["pos_embed"]
    n = x.shape[1]
    for i in range(shapes["num_layers"]):
        blk = params[f"blocks_{i}"]
        y = ops.layer_norm(x, blk["norm1"])
        qkv = ops.dense(y, blk["attn"]["qkv"], mode)
        qkv = qkv.reshape(b, n, 3, heads, d // heads)
        y = ops.attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], mode)
        x = x + ops.dense(y.reshape(b, n, d), blk["attn"]["proj"], mode)
        x = x + ops.mlp(ops.layer_norm(x, blk["norm2"]), blk["mlp"], mode)
    x = ops.layer_norm(x, params["norm"])[:, 0]
    return ops.dense(x, params["head"], mode)
