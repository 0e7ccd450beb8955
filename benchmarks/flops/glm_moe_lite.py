"""Operations and bytes a ``glm4_moe_lite`` decoder requires on this chip,
from its shapes (``seq_len`` among them). Multiply-accumulates of the matrix
products only: MLA's five projections, causal QK^T and PV (each token against
itself and the tokens before it: n (n + 1) / 2 pairs a head), the dense
SwiGLU, router, shared expert, the routed experts held here at the
expectation of uniform routing (``num_experts_per_tok`` x held / published rows
a token), the MTP module's ``eh_proj`` and block, and both heads over the
vocabulary held. A training step requires the forward pass and twice as much
again for the backward pass. Recomputation, the optimizer, norms, rotary
embedding and routing's data movement are not counted."""

from __future__ import annotations


def _mla_macs(s: dict) -> int:
    """Projections, per token."""
    d, h = s["hidden_size"], s["num_attention_heads"]
    nope, rope, dv = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    return (d * s["q_lora_rank"] + s["q_lora_rank"] * h * (nope + rope)
            + d * (s["kv_lora_rank"] + rope)
            + s["kv_lora_rank"] * h * (nope + dv) + h * dv * d)


def _score_macs(s: dict) -> int:
    """Causal QK^T and PV of one layer, per sequence."""
    n, h = s["seq_len"], s["num_attention_heads"]
    pairs = n * (n + 1) // 2
    return pairs * h * (s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
                        + s["v_head_dim"])


def _expert_macs(s: dict) -> int:
    """One SwiGLU expert, per row."""
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def expected_rows_per_token(s: dict) -> float:
    return (s["num_experts_per_tok"] * s["n_routed_experts"]
            / s["n_routed_experts_published"])


def _layers(s: dict) -> tuple:
    """(dense layers, expert layers) with the MTP module's block."""
    dense = min(s["first_k_dense_replace"], s["num_hidden_layers"])
    return dense, (s["num_hidden_layers"] - dense
                   + s["num_nextn_predict_layers"])


def forward_macs(s: dict) -> float:
    """Per sequence."""
    n, d = s["seq_len"], s["hidden_size"]
    dense, sparse = _layers(s)
    per_token = (dense + sparse) * _mla_macs(s) \
        + dense * 3 * d * s["intermediate_size"] \
        + sparse * (d * s["n_routed_experts_published"]
                    + s["n_shared_experts"] * _expert_macs(s)
                    + expected_rows_per_token(s) * _expert_macs(s)) \
        + s["num_nextn_predict_layers"] * 2 * d * d \
        + (1 + s["num_nextn_predict_layers"]) * d * s["vocab_size"]
    return n * per_token + (dense + sparse) * _score_macs(s)


def train_flops(s: dict) -> float:
    """Per sequence: forward, and the backward pass's two products per
    product."""
    return 3 * 2 * forward_macs(s)


def attention_work(s: dict, batch: int) -> dict:
    """The attention cores of a step of ``batch`` sequences (every block's,
    the MTP module's among them), forward and backward: causal QK^T and PV
    and their four backward products, and the least bytes a kernel has to
    move (q, k, v in and the output out going forward; those four and the
    output's gradient in, three gradients out going back) in bfloat16. The
    projections are not the core's."""
    n, h = s["seq_len"], s["num_attention_heads"]
    layers = sum(_layers(s))
    width = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    flops = 3 * 2 * _score_macs(s) * layers * batch
    bytes_ = (4 + 8) * (n * h * width * 2) * layers * batch
    return {"flops": flops, "bytes": bytes_}


def expert_matmul_work(s: dict, rows: float, layer_steps: int) -> dict:
    """The grouped products of the routed experts over ``rows`` (token,
    expert) rows in all, spread over ``layer_steps`` executions of an expert
    layer: three products a row forward and twice that back; bytes: each row
    in and out (forward: in, out; backward: the row, the output's gradient
    in, the row's gradient out) and, an execution, the held experts' weights
    read forward, read again and their gradients written going back, all in
    bfloat16."""
    d = s["hidden_size"]
    weights = s["n_routed_experts"] * _expert_macs(s) * 2
    return {"flops": 3 * 2 * _expert_macs(s) * rows,
            "bytes": 5 * rows * d * 2 + 3 * weights * layer_steps}
