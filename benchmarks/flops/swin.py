"""Operations and bytes a Swin Transformer requires, from its shapes (see
``flops/vit.py`` for what is counted). Attention runs inside windows of
``window_size``^2 tokens, so its products cost tokens x window x width."""

from __future__ import annotations


def _stages(shapes: dict):
    dim = shapes["hidden_size"]
    res = shapes["image_size"] // shapes["patch_size"]
    for depth in shapes["depths"]:
        yield depth, dim, res * res, min(shapes["window_size"], res) ** 2
        dim, res = dim * 2, res // 2


def forward_macs(shapes: dict) -> int:
    """Per image."""
    stages = list(_stages(shapes))
    total = stages[0][2] * (shapes["patch_size"] ** 2 * 3) * shapes["hidden_size"]
    for i, (depth, dim, tokens, window) in enumerate(stages):
        hidden = int(dim * shapes["mlp_ratio"])
        block = tokens * (3 * dim * dim + dim * dim + 2 * dim * hidden) \
            + 2 * tokens * window * dim
        total += depth * block
        if i < len(stages) - 1:
            total += (tokens // 4) * (4 * dim) * (2 * dim)
    return total + stages[-1][1] * shapes["num_classes"]


def train_flops(shapes: dict) -> int:
    return 3 * 2 * forward_macs(shapes)


def attention_work(shapes: dict, batch: int) -> dict:
    """Window attention of every block, forward and backward: the products'
    operations and the least bytes (q, k, v, output and their gradients once
    each, bfloat16). Partition, shift and merge are index arithmetic a kernel
    can fold into its reads, so they add no byte here; the time they take in
    the program is in the measured denominator."""
    flops = bytes_ = 0
    for depth, dim, tokens, window in _stages(shapes):
        flops += depth * 3 * 2 * (2 * tokens * window * dim)
        bytes_ += depth * (4 + 8) * (tokens * dim * 2)
    return {"flops": flops * batch, "bytes": bytes_ * batch}
