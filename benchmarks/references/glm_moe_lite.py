"""Plain reference of ``glm4_moe_lite`` (GLM-4.7-Flash,
https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json): a causal
pre-norm decoder with multi-head latent attention, sigmoid-routed sparse
experts with a shared expert (``noaux_tc``) and one multi-token-prediction
module (DeepSeek-V3 report, arXiv:2412.19437, section 2.2). Straight
``jax.numpy``, float32, no kernels, no cache; the same share of a stated
deployment as the program is given: experts ``first_expert .. first_expert +
n_routed_experts - 1`` of ``n_routed_experts_published`` and ``vocab_size``
rows of the vocabulary. What the absent experts would add is left out.
Parameter names are the program's, so one tree serves both. Imports nothing
of the program.

Departures forced by memory, none of them in the mathematics: attention
takes the queries in blocks of ``ATTENTION_ROWS`` against the keys up to the
block's end, and every block of layers is recomputed in the backward pass
(``jax.checkpoint``), so that a 4,096-token row in float32 fits beside 11 GB
of state. The routed experts are computed the plain way: every held expert on
every token, weighted by the router's weight (nought where it did not choose
the expert); no sort, no gather.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ops

ATTENTION_ROWS = 1024


def _kernel(n_in: int, n_out: int) -> dict:
    return {"kernel": ((n_in, n_out), "normal")}


def _scale(n: int) -> dict:
    return {"scale": ((n,), "ones")}


def _swiglu_spec(d: int, f: int) -> dict:
    return {"gate": _kernel(d, f), "up": _kernel(d, f), "down": _kernel(f, d)}


def _block_spec(s: dict, dense_ffn: bool) -> dict:
    d, h = s["hidden_size"], s["num_attention_heads"]
    nope, rope, dv = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                      s["v_head_dim"])
    spec = {
        "attn_norm": _scale(d), "ffn_norm": _scale(d),
        "attn": {"q_a": _kernel(d, s["q_lora_rank"]),
                 "q_a_norm": _scale(s["q_lora_rank"]),
                 "q_b": _kernel(s["q_lora_rank"], h * (nope + rope)),
                 "kv_a": _kernel(d, s["kv_lora_rank"] + rope),
                 "kv_a_norm": _scale(s["kv_lora_rank"]),
                 "kv_b": _kernel(s["kv_lora_rank"], h * (nope + dv)),
                 "o": _kernel(h * dv, d)},
    }
    if dense_ffn:
        spec["mlp"] = _swiglu_spec(d, s["intermediate_size"])
    else:
        held, f = s["n_routed_experts"], s["moe_intermediate_size"]
        spec["moe"] = {
            "router_kernel": ((d, s["n_routed_experts_published"]), "normal"),
            # held fixed at seeded values that are not nought, so that
            # leaving it out of the choice shows
            "correction_bias": (
                (s["n_routed_experts_published"],),
                f"bias:{s.get('first_expert', 0)}:{held}"),
            "experts_gate": ((held, d, f), "normal"),
            "experts_up": ((held, d, f), "normal"),
            "experts_down": ((held, f, d), "normal"),
            "shared": _swiglu_spec(d, f * s["n_shared_experts"]),
        }
    return spec


def param_spec(s: dict) -> dict:
    d, v = s["hidden_size"], s["vocab_size"]
    spec = {"embed": {"embedding": ((v, d), "normal")},
            "head": _kernel(d, v), "norm": _scale(d)}
    for i in range(s["num_hidden_layers"]):
        spec[f"layers_{i}"] = _block_spec(s, i < s["first_k_dense_replace"])
    if s["num_nextn_predict_layers"]:
        spec["mtp"] = {"enorm": _scale(d), "hnorm": _scale(d),
                       "eh_proj": _kernel(2 * d, d),
                       "block": _block_spec(s, False), "norm": _scale(d)}
    return spec


BIAS_SPREAD = 0.1     # correction biases lie in +-BIAS_SPREAD


def _bias(key, n: int, first: int, held: int):
    """Correction biases of ``n`` experts: the held experts get the values
    ``BIAS_SPREAD * linspace(-1, 1, held)`` in an order drawn from the seed,
    the absent ones the same over their count. Which expert is favoured
    changes with the seed; how many rows the router sends to this chip hardly
    does (with independent draws the held experts' share of the rows, and
    with it the step's time, moved by 1.4 % from seed to seed: my chip runs,
    PR 32)."""
    k_held, k_rest = jax.random.split(key)
    mine = jax.random.permutation(k_held, jnp.linspace(-1.0, 1.0, held))
    if held == n:
        return BIAS_SPREAD * mine
    rest = jax.random.permutation(k_rest, jnp.linspace(-1.0, 1.0, n - held))
    return BIAS_SPREAD * jnp.concatenate(
        [rest[:first], mine, rest[first:]]).astype(jnp.float32)


def make_params(spec: dict, seed: int):
    """Weights from the seed in one jitted call: N(0, 0.02) matrices and
    embeddings, unit norm scales, correction biases in +-0.1 (``_bias``; the
    sigmoids they are added to spread by some 0.2). float32."""
    leaves, treedef = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[1], str))

    def build(key):
        out = []
        for i, (shape, kind) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            if kind == "normal":
                out.append(0.02 * jax.random.normal(k, shape, jnp.float32))
            elif kind.startswith("bias:"):
                first, held = (int(x) for x in kind.split(":")[1:])
                out.append(_bias(k, shape[0], first, held))
            else:
                out.append(jnp.ones(shape, jnp.float32))
        return out
    return jax.tree.unflatten(treedef, jax.jit(build)(jax.random.key(seed)))


def rms_norm(x, p, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * p["scale"]


def rotary(x, theta):
    """(..., n, r): positions 0..n-1, dimension i paired with i + r/2."""
    n, r = x.shape[-2], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def swiglu(x, p, mode):
    return ops.dense(jax.nn.silu(ops.dense(x, p["gate"], mode))
                     * ops.dense(x, p["up"], mode), p["down"], mode)


def causal_attention(q, k, v, mode):
    """softmax(q k^T / sqrt(d), causal) v over (b, h, n, d), the queries in
    blocks of rows."""
    n, scale = q.shape[2], q.shape[-1] ** -0.5

    @jax.checkpoint
    def rows(q_blk, k_to, v_to, lo):
        s = ops.einsum("bhqd,bhkd->bhqk", q_blk * scale, k_to, mode)
        mask = (lo + jnp.arange(q_blk.shape[2]))[:, None] \
            >= jnp.arange(k_to.shape[2])[None, :]
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        return ops.einsum("bhqk,bhkd->bhqd", p, v_to, mode)

    out = [rows(q[:, :, lo:lo + ATTENTION_ROWS], k[:, :, :lo + ATTENTION_ROWS],
                v[:, :, :lo + ATTENTION_ROWS], lo)
           for lo in range(0, n, ATTENTION_ROWS)]
    return jnp.concatenate(out, axis=2)


def mla(x, p, s, mode):
    b, n, _ = x.shape
    h, nope, rope, dv = (s["num_attention_heads"], s["qk_nope_head_dim"],
                         s["qk_rope_head_dim"], s["v_head_dim"])
    eps, theta, r_kv = s["rms_norm_eps"], s["rope_theta"], s["kv_lora_rank"]
    c_q = rms_norm(ops.dense(x, p["q_a"], mode), p["q_a_norm"], eps)
    q = ops.dense(c_q, p["q_b"], mode).reshape(b, n, h, nope + rope)
    q = q.transpose(0, 2, 1, 3)
    kv = ops.dense(x, p["kv_a"], mode)
    c_kv = rms_norm(kv[..., :r_kv], p["kv_a_norm"], eps)
    k_rope = rotary(kv[..., r_kv:], theta)                 # one for all heads
    kv = ops.dense(c_kv, p["kv_b"], mode).reshape(b, n, h, nope + dv)
    kv = kv.transpose(0, 2, 1, 3)
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], theta)], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_rope[:, None], (b, h, n, rope))], -1)
    out = causal_attention(q, k, kv[..., nope:], mode)
    return ops.dense(out.transpose(0, 2, 1, 3).reshape(b, n, h * dv), p["o"],
                     mode)


def route(x, p, s, bias_in_choice=True):
    """(chosen experts (T, k), their weights (T, k)) over all the published
    experts, float32 whatever the mode: sigmoid scores, the k largest of
    score + bias, weights from the scores alone."""
    scores = jax.nn.sigmoid(ops.einsum("td,de->te", x, p["router_kernel"],
                                       "f32"))
    ranked = scores + p["correction_bias"] if bias_in_choice else scores
    idx = jnp.argsort(-ranked, axis=-1, stable=True)[
        :, : s["num_experts_per_tok"]]
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weights = s["routed_scaling_factor"] * chosen / (
        jnp.sum(chosen, -1, keepdims=True) + 1e-20)
    return idx, weights


def expert_layer(x, p, s, mode, bias_in_choice=True):
    """The held experts' part of the routed result plus the shared expert."""
    b, n, d = x.shape
    tokens = x.reshape(b * n, d)
    idx, weights = route(tokens, p, s, bias_in_choice)
    held = jnp.arange(s["n_routed_experts"]) + s.get("first_expert", 0)
    # (held, T): the router's weight of each held expert, nought where the
    # token did not choose it
    w = jnp.sum(jnp.where(idx[None] == held[:, None, None], weights[None], 0.0),
                axis=-1)

    def one_expert(y, xs):
        w_e, gate, up, down = xs
        expert = {"gate": {"kernel": gate}, "up": {"kernel": up},
                  "down": {"kernel": down}}
        return y + w_e[:, None] * swiglu(tokens, expert, mode), None
    # a loop over the held experts, rolled: eight unrolled copies of three
    # float32 products made a 1 GB program of the cell's reference
    y, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(tokens),
                        (w, p["experts_gate"], p["experts_up"],
                         p["experts_down"]))
    return y.reshape(b, n, d) + swiglu(x, p["shared"], mode), idx


def block(x, p, s, mode, bias_in_choice=True):
    eps = s["rms_norm_eps"]
    h = x + mla(rms_norm(x, p["attn_norm"], eps), p["attn"], s, mode)
    y = rms_norm(h, p["ffn_norm"], eps)
    if "mlp" in p:
        return h + swiglu(y, p["mlp"], mode), None
    y, idx = expert_layer(y, p["moe"], s, mode, bias_in_choice)
    return h + y, idx


def hidden_states(params, tokens, next_tokens, s, mode, bias_in_choice=True,
                  remat=True):
    """tokens, next_tokens (b, n) -> (normed hidden states before the head,
    one per head: main, and MTP where the model has the module; the routers'
    choices by layer)."""
    eps = s["rms_norm_eps"]
    run = functools.partial(block, s=s, mode=mode,
                            bias_in_choice=bias_in_choice)
    if remat:
        run = jax.checkpoint(run)
    x = params["embed"]["embedding"][tokens]
    choices = {}
    for i in range(s["num_hidden_layers"]):
        x, idx = run(x, params[f"layers_{i}"])
        if idx is not None:
            choices[f"layers_{i}"] = idx
    hidden = [rms_norm(x, params["norm"], eps)]
    if s["num_nextn_predict_layers"]:
        m = params["mtp"]
        joined = jnp.concatenate(
            [rms_norm(params["embed"]["embedding"][next_tokens], m["enorm"],
                      eps), rms_norm(x, m["hnorm"], eps)], axis=-1)
        y, idx = run(ops.dense(joined, m["eh_proj"], mode), m["block"])
        choices["mtp/block"] = idx
        hidden.append(rms_norm(y, m["norm"], eps))
    return hidden, choices


def forward(params, tokens, next_tokens, s, mode, bias_in_choice=True,
            remat=True):
    """float32 logits (b, n, V) per head: of token i + 1, and of token
    i + 2 from the MTP module."""
    hidden, _ = hidden_states(params, tokens, next_tokens, s, mode,
                              bias_in_choice, remat)
    return [ops.dense(h, params["head"], mode) for h in hidden]


def loss_sums(params, rows, s, mode, bias_in_choice=True):
    """rows (b, n + 1) of token ids -> per head, the sum over the positions
    that have a target of the cross entropy, and how many those are."""
    n = rows.shape[1] - 1
    logits = forward(params, rows[:, :-1], rows[:, 1:], s, mode,
                     bias_in_choice)
    out = []
    for ahead, lg in enumerate(logits, start=1):
        lg, targets = lg[:, : n - ahead + 1], rows[:, ahead:]
        picked = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
        out.append((jnp.sum(jax.nn.logsumexp(lg, axis=-1) - picked),
                    rows.shape[0] * (n - ahead + 1)))
    return out
