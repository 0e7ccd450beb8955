"""Operations and bytes a Vision Transformer requires, from its shapes.
Multiply-accumulates of the matrix products only (patch embedding, qkv,
QK^T, PV, projection, MLP, head); a training step requires the forward pass
and twice as much again for the backward pass. Recomputation, the optimizer
and the moving average are not counted."""

from __future__ import annotations


def _tokens(shapes: dict) -> int:
    return (shapes["image_size"] // shapes["patch_size"]) ** 2 + 1


def forward_macs(shapes: dict) -> int:
    """Per image."""
    d, t = shapes["hidden_size"], _tokens(shapes)
    hidden = int(d * shapes["mlp_ratio"])
    patch = (t - 1) * (shapes["patch_size"] ** 2 * 3) * d
    layer = t * (3 * d * d + d * d + 2 * d * hidden) + 2 * t * t * d
    return patch + shapes["num_layers"] * layer + d * shapes["num_classes"]


def train_flops(shapes: dict) -> int:
    """Per image: forward, and the backward pass's two products per product."""
    return 3 * 2 * forward_macs(shapes)


def attention_work(shapes: dict, batch: int) -> dict:
    """QK^T, softmax and PV of every layer, forward and backward, for a batch:
    the products' operations, and the least bytes a kernel has to move (q, k,
    v in and the output out going forward; those four and the output's
    gradient in, three gradients out going back), in the configuration's
    bfloat16. The T x T scores need never leave the chip's fast memory."""
    d, t, layers = shapes["hidden_size"], _tokens(shapes), shapes["num_layers"]
    flops = 3 * 2 * (2 * t * t * d) * layers * batch
    bytes_ = (4 + 8) * (t * d * 2) * layers * batch
    return {"flops": flops, "bytes": bytes_}
