"""``glm4_moe_lite`` (GLM-4.7-Flash): a causal decoder with latent attention
(MLA), sigmoid-routed sparse experts with a shared expert, and a
multi-token-prediction module. Training path only: keys and values are
computed in full, there is no latent cache.

With ``x`` a token's hidden state and ``RMSNorm`` at eps 1e-5:

- block: ``h = x + MLA(RMSNorm(x))``, ``out = h + FFN(RMSNorm(h))``; ``FFN`` is
  a SwiGLU MLP in the first ``first_dense`` layers and the expert layer
  (``parallel/moe.py::HeldExpertsMlp``) elsewhere; no biases anywhere.
- MLA: ``c_q = RMSNorm(x W_qa)``; per head ``[q_nope, q_rope] = c_q W_qb``;
  ``[c_kv, k_rope] = x W_kva``, ``c_kv = RMSNorm(c_kv)``; per head ``[k_nope,
  v] = c_kv W_kvb``; ``k_rope`` is one vector shared by the heads; rotary
  embedding over all rope dimensions (pairs ``(i, i + r/2)``) on ``q_rope``
  and ``k_rope``; scores ``(q_nope . k_nope + q_rope . k_rope) / sqrt(nope +
  rope)``, causal, softmax in float32; output ``concat_heads(P v) W_o``.
- MTP (after the last block, before the final norm): ``h' = W_eh
  [RMSNorm(Emb(t_{i+1})) ; RMSNorm(h_i)]``, one expert-layer block on ``h'``
  under the same mask, its own final RMSNorm, the model's embedding and
  head; it predicts ``t_{i+2}``.

A chip holds its share of a stated deployment: ``experts_held`` of
``n_routed_experts`` a layer from ``first_expert`` on, ``vocab_size`` rows of
the vocabulary. The attention core picks its own path
(``ops/pallas/flash_attention.py::select_path``): the fused causal kernels on
a TPU at whole 128-row blocks and 128-lane head widths, the lax mathematics
elsewhere. Each layer tallies a ``kernel`` flight event (``mla_attention``)
with the path it took and ``forward_kept``: whether the block's backward pass
finds the core's output and logsumexp kept (``decoder.remat_block``: all else
in a block is computed again). The skeleton round the blocks (embedding,
remat, final norm, head, the registry factory) is ``decoder.py``'s, shared
with ``mellum.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ...obs import flight
from ...ops.pallas import flash_attention as fused
from ...parallel.moe import HeldExpertsMlp, SwiGLU
from . import decoder
from .decoder import RMSNorm, _dense, _factory, forward_kept, remat_block


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    """``config.json``'s keys, with the chip's share beside them."""
    vocab_size: int = 154880
    hidden_size: int = 2048
    num_hidden_layers: int = 47
    first_k_dense_replace: int = 1
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    n_routed_experts: int = 64
    experts_held: int = 64
    first_expert: int = 0
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.8
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    num_nextn_predict_layers: int = 1

    def block(self, i: int, dtype, name: str):
        return _RematBlock(self, i < self.first_k_dense_replace, dtype,
                           name=name)

    def mtp(self, dtype, name: str):
        return MTP(self, dtype, name=name) \
            if self.num_nextn_predict_layers else None


def rotary(x: jax.Array, theta: float) -> jax.Array:
    """``decoder.rotary`` at the unscaled frequencies of ``theta``."""
    return decoder.rotary(x, decoder.rope_inv_freq(theta, x.shape[-1]))


class MLA(nn.Module):
    """Multi-head latent attention, keys and values computed in full."""
    cfg: DecoderConfig
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        b, n, _ = x.shape
        h, nope, rope, dv = (c.num_attention_heads, c.qk_nope_head_dim,
                             c.qk_rope_head_dim, c.v_head_dim)
        norm = lambda name: RMSNorm(c.rms_norm_eps, self.dtype, name=name)
        c_q = norm("q_a_norm")(_dense(c.q_lora_rank, self.dtype, "q_a")(x))
        # ``head_split`` (train/steps.py::STEP_SCOPES): the layout work at
        # the core's boundary that is the model's own; opened round the ops,
        # never round a module or the core
        q = _dense(h * (nope + rope), self.dtype, "q_b")(c_q)
        with jax.named_scope("head_split"):
            q = q.reshape(b, n, h, nope + rope).transpose(0, 2, 1, 3)
        kv = _dense(c.kv_lora_rank + rope, self.dtype, "kv_a")(x)
        with jax.named_scope("head_split"):
            c_kv = kv[..., : c.kv_lora_rank]
        c_kv = norm("kv_a_norm")(c_kv)
        with jax.named_scope("head_split"):
            k_rope = kv[..., c.kv_lora_rank:]
        k_rope = rotary(k_rope, c.rope_theta)                     # (b, n, r)
        kv = _dense(h * (nope + dv), self.dtype, "kv_b")(c_kv)
        with jax.named_scope("head_split"):
            kv = kv.reshape(b, n, h, nope + dv).transpose(0, 2, 1, 3)
            q_nope, q_rope = q[..., :nope], q[..., nope:]
        q_rope = rotary(q_rope, c.rope_theta)
        with jax.named_scope("head_split"):
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_rope[:, None], (b, h, n, rope))], axis=-1)
            v = kv[..., nope:]
        path = fused.select_path(
            n, nope + rope, initializing=self.is_initializing()) \
            if nope + rope == dv else "lax"
        kept = forward_kept(self, path)
        flight.tally("kernel", ("mla_attention", path, kept, n, h, dv),
                     member="/".join(self.path), name="mla_attention",
                     path=path, shape=[b, h, n, dv], forward_kept=kept)
        with jax.named_scope("mla_core"):
            out = fused.causal_attention(q, k, v, (nope + rope) ** -0.5, path)
        with jax.named_scope("head_split"):
            out = out.transpose(0, 2, 1, 3).reshape(b, n, h * dv)
        return _dense(c.hidden_size, self.dtype, "o")(out)


class DecoderBlock(nn.Module):
    cfg: DecoderConfig
    dense_ffn: bool
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        norm = lambda name: RMSNorm(c.rms_norm_eps, self.dtype, name=name)
        h = x + MLA(c, self.dtype, name="attn")(norm("attn_norm")(x))
        if self.dense_ffn:
            ffn = SwiGLU(c.intermediate_size, self.dtype, name="mlp")
        else:
            ffn = HeldExpertsMlp(
                num_experts=c.n_routed_experts, held=c.experts_held,
                first=c.first_expert, top_k=c.num_experts_per_tok,
                hidden=c.moe_intermediate_size,
                shared_experts=c.n_shared_experts,
                routed_scale=c.routed_scaling_factor, dtype=self.dtype,
                name="moe")
        return h + ffn(norm("ffn_norm")(h))


_RematBlock = remat_block(DecoderBlock)


class MTP(nn.Module):
    """One multi-token-prediction module (DeepSeek-V3 report, section 2.2)."""
    cfg: DecoderConfig
    dtype: Any

    @nn.compact
    def __call__(self, hidden, next_embedded):
        c = self.cfg
        norm = lambda name: RMSNorm(c.rms_norm_eps, self.dtype, name=name)
        joined = jnp.concatenate([norm("enorm")(next_embedded),
                                  norm("hnorm")(hidden)], axis=-1)
        x = _dense(c.hidden_size, self.dtype, "eh_proj")(joined)
        # an expert-layer block, as the layers after the dense ones are
        x = c.block(c.num_hidden_layers, self.dtype, name="block")(x)
        return norm("norm")(x)


# GLM-4.7-Flash (30B-A3B; ``DecoderConfig``'s defaults are its published
# config, which no single chip holds) as one chip's share of 8-way expert
# parallelism: experts 0-7 of 64 and rows
# 0-19,359 of the vocabulary, the dense layer and four expert layers (the
# other 42 lie on further chips as pipeline stages)
glm47_flash_ep8 = _factory("glm47_flash_ep8", DecoderConfig, vocab_size=19360,
                           num_hidden_layers=5, experts_held=8)
# a CPU-sized decoder of the same shape of block, for tests and smoke runs
glm_moe_lite_micro = _factory(
    "glm_moe_lite_micro", DecoderConfig, vocab_size=512, hidden_size=64,
    num_hidden_layers=3, intermediate_size=160, moe_intermediate_size=48,
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=24,
    qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32,
    n_routed_experts=16, experts_held=4)
