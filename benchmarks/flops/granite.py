"""Operations and bytes a ``granite`` decoder requires on this chip, from its
shapes (``seq_len`` among them). Multiply-accumulates of the matrix products
only: a Mamba layer's two projections, an attention layer's four, every
layer's SwiGLU MLP (three products of the hidden size by
``intermediate_size``), QK^T and PV of the full layers over the pairs the
causal mask allows (n (n + 1) / 2 a head), the scans (``scan_macs``), and
the tied head over the vocabulary held. A training step requires the
forward pass and twice as much again for the backward pass. Recomputation,
the optimizer, norms, the convolution and the gates are not counted."""

from __future__ import annotations

MAMBA, ATTENTION = "mamba", "attention"


def _kinds(s: dict) -> list:
    period = s["layer_types"].split(",")
    return [period[i % len(period)] for i in range(s["num_hidden_layers"])]


def _inner(s: dict) -> int:
    return s["mamba_n_heads"] * s["mamba_d_head"]


def _mamba_projection_macs(s: dict) -> int:
    """in_proj (to z, xBC and dt) and out_proj, per token."""
    d, inner = s["hidden_size"], _inner(s)
    conv = inner + 2 * s["mamba_n_groups"] * s["mamba_d_state"]
    return d * (inner + conv + s["mamba_n_heads"]) + inner * d


def _attention_projection_macs(s: dict) -> int:
    """q, k, v and o, per token."""
    d, h = s["hidden_size"], s["num_attention_heads"]
    return 2 * d * d + 2 * d * (d // h) * s["num_key_value_heads"]


def _mlp_macs(s: dict) -> int:
    return 3 * s["hidden_size"] * s["intermediate_size"]


def pairs(s: dict) -> int:
    """(query, key) pairs a head of a full layer attends over, per
    sequence."""
    n = s["seq_len"]
    return n * (n + 1) // 2


def _score_macs(s: dict) -> int:
    """QK^T and PV of one full layer, per sequence."""
    d, h = s["hidden_size"], s["num_attention_heads"]
    return pairs(s) * h * 2 * (d // h)


def scan_macs(s: dict) -> float:
    """One Mamba layer's scan at the published chunk ``Q``, per sequence:
    within chunks, ``C Bᵀ`` over the lower triangle (n (Q + 1) / 2 pairs, a
    state's width a group) and its product with ``Δ x`` (the same pairs, a
    head's width a head); each chunk's final state and the states'
    contribution to the outputs, ``n H P N`` each. The state passed from chunk
    to chunk is elementwise and not counted. What the scan requires whatever
    implements it."""
    n, q = s["seq_len"], s["mamba_chunk_size"]
    g, st = s["mamba_n_groups"], s["mamba_d_state"]
    hp = _inner(s)
    return n * (q + 1) / 2 * (g * st + hp) + 2 * n * hp * st


def forward_macs(s: dict) -> float:
    """Per sequence."""
    kinds = _kinds(s)
    per_token = sum(_mlp_macs(s) + (_mamba_projection_macs(s) if k == MAMBA
                                    else _attention_projection_macs(s))
                    for k in kinds) + s["hidden_size"] * s["vocab_size"]
    return s["seq_len"] * per_token + kinds.count(ATTENTION) * _score_macs(s) \
        + kinds.count(MAMBA) * scan_macs(s)


def train_flops(s: dict) -> float:
    """Per sequence: forward, and the backward pass's two products per
    product."""
    return 3 * 2 * forward_macs(s)


def ssd_scan_work(s: dict, batch: int) -> dict:
    """The Mamba layers' scans over a step of ``batch`` sequences, forward and
    backward: ``scan_macs`` and its backward products; and the least bytes a
    kernel has to move, whatever implements it: bfloat16 x, B and C in and y
    out and the float32 step sizes Δ in going forward; x, B, C, Δ and y's
    gradient in and the gradients of x, B, C and Δ out going back. The
    projections, the convolution, the skip and the gated norm are not the
    scan's."""
    layers = _kinds(s).count(MAMBA)
    hp = _inner(s)
    gn = s["mamba_n_groups"] * s["mamba_d_state"]
    per_token = 5 * hp * 2 + 3 * 2 * gn * 2 + 3 * s["mamba_n_heads"] * 4
    return {"flops": 3 * 2 * scan_macs(s) * layers * batch,
            "bytes": per_token * s["seq_len"] * layers * batch}


def full_attention_work(s: dict, batch: int) -> dict:
    """The full layers' attention cores over a step of ``batch`` sequences,
    forward and backward: QK^T and PV over the causal pairs and their four
    backward products, and the least bytes a kernel has to move in
    bfloat16: q and the output for every query head and k, v for every
    key/value head going forward; those four and the output's gradient in,
    q's gradient and k's, v's out going back."""
    layers = _kinds(s).count(ATTENTION)
    h, kv = s["num_attention_heads"], s["num_key_value_heads"]
    head_rows = (2 * h + 2 * kv) + (3 * h + 2 * kv) + (h + 2 * kv)
    return {"flops": 3 * 2 * _score_macs(s) * layers * batch,
            "bytes": head_rows * s["seq_len"] * (s["hidden_size"] // h) * 2
            * layers * batch}
