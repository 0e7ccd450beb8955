"""Operations and bytes a ``mellum`` decoder requires on this chip, from its
shapes (``seq_len`` among them). Multiply-accumulates of the matrix products
only: the attention's four projections, QK^T and PV over the pairs a layer's
mask allows (a full layer: each token against itself and the tokens before
it, n (n + 1) / 2 pairs a head; a sliding layer of window w: w (w + 1) / 2 +
(n - w) w), the router, the routed experts held here at the expectation of
uniform routing (``num_experts_per_tok`` x held / published rows a token) and
the head over the vocabulary held. A training step requires the forward pass
and twice as much again for the backward pass. Recomputation, the optimizer,
norms, rotary embedding and routing's data movement are not counted."""

from __future__ import annotations

SLIDING = "sliding_attention"


def _kinds(s: dict) -> list:
    period = s["layer_types"].split(",")
    return [period[i % len(period)] for i in range(s["num_hidden_layers"])]


def pairs(s: dict, kind: str) -> int:
    """(query, key) pairs a head of one layer of ``kind`` attends over, per
    sequence."""
    n, w = s["seq_len"], min(s["sliding_window"], s["seq_len"])
    if kind == SLIDING:
        return w * (w + 1) // 2 + (n - w) * w
    return n * (n + 1) // 2


def _projection_macs(s: dict) -> int:
    """q, k, v and o, per token."""
    d, hd = s["hidden_size"], s["head_dim"]
    return 2 * d * hd * (s["num_attention_heads"] + s["num_key_value_heads"])


def _score_macs(s: dict, kind: str) -> int:
    """QK^T and PV of one layer of ``kind``, per sequence."""
    return pairs(s, kind) * s["num_attention_heads"] * 2 * s["head_dim"]


def _expert_macs(s: dict) -> int:
    """One SwiGLU expert, per row."""
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def expected_rows_per_token(s: dict) -> float:
    return (s["num_experts_per_tok"] * s["num_experts"]
            / s["num_experts_published"])


def forward_macs(s: dict) -> float:
    """Per sequence."""
    d, layers = s["hidden_size"], s["num_hidden_layers"]
    per_token = layers * (_projection_macs(s)
                          + d * s["num_experts_published"]
                          + expected_rows_per_token(s) * _expert_macs(s)) \
        + d * s["vocab_size"]
    return s["seq_len"] * per_token \
        + sum(_score_macs(s, kind) for kind in _kinds(s))


def train_flops(s: dict) -> float:
    """Per sequence: forward, and the backward pass's two products per
    product."""
    return 3 * 2 * forward_macs(s)


def _attention_work(s: dict, batch: int, kind: str) -> dict:
    """The attention cores of the layers of ``kind`` over a step of ``batch``
    sequences, forward and backward: QK^T and PV over the pairs the mask
    allows and their four backward products, and the least bytes a kernel has
    to move in bfloat16, whatever implements it: q and the output for every
    query head and k, v for every key/value head going forward; those four
    and the output's gradient in, q's gradient and k's, v's out going back.
    The projections, norms and rotary embedding are not the core's."""
    layers = _kinds(s).count(kind)
    h, kv = s["num_attention_heads"], s["num_key_value_heads"]
    head_rows = (2 * h + 2 * kv) + (3 * h + 2 * kv) + (h + 2 * kv)
    return {"flops": 3 * 2 * _score_macs(s, kind) * layers * batch,
            "bytes": head_rows * s["seq_len"] * s["head_dim"] * 2
            * layers * batch}


def sliding_attention_work(s: dict, batch: int) -> dict:
    return _attention_work(s, batch, SLIDING)


def full_attention_work(s: dict, batch: int) -> dict:
    return _attention_work(s, batch, "full_attention")


def expert_matmul_work(s: dict, rows: float, layer_steps: int) -> dict:
    """The grouped products of the routed experts over ``rows`` (token,
    expert) rows in all, spread over ``layer_steps`` executions of an expert
    layer: three products a row forward and twice that back; bytes: each row
    in and out (forward: in, out; backward: the row, the output's gradient
    in, the row's gradient out) and, an execution, the held experts' weights
    read forward, read again and their gradients written going back, all in
    bfloat16."""
    d = s["hidden_size"]
    weights = s["num_experts"] * _expert_macs(s) * 2
    return {"flops": 3 * 2 * _expert_macs(s) * rows,
            "bytes": 5 * rows * d * 2 + 3 * weights * layer_steps}
