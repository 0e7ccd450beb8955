"""Plain reference of ``mellum`` (Mellum2-12B-A2.5B-Instruct,
https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json):
a causal pre-norm decoder with grouped-query attention, sliding-window layers
among full ones (three to one), a rotary embedding of its own for each kind
(unscaled in the sliding layers, YaRN in the full ones) and softmax-routed
sparse experts in every layer. Straight ``jax.numpy``, float32, no kernels, no
cache; the same share of a stated deployment as the program is given: experts
``first_expert .. first_expert + num_experts - 1`` of
``num_experts_published`` and ``vocab_size`` rows of the vocabulary. What the
absent experts would add is left out. Parameter names are the program's, so
one tree serves both. Imports nothing of the program.

With ``x`` a token's hidden state, RMSNorm at ``rms_norm_eps``, no biases:

- block: ``h = x + Attn(RMSNorm(x))``, ``out = h + MoE(RMSNorm(h))``;
- Attn: ``q = x W_q`` (heads x head_dim), ``k, v = x W_k, x W_v`` (kv heads x
  head_dim); a per-head RMSNorm on ``q`` and ``k``; rotary embedding over all
  of head_dim (rotate-half); K and V repeated so that query head ``j`` reads
  key/value head ``j // (heads / kv heads)``; scores ``q . k / sqrt(head_dim)``,
  query ``i`` sees keys ``i - sliding_window + 1 .. i`` in a sliding layer and
  ``0 .. i`` in a full one, the mask a comparison of positions; softmax;
  ``W_o``;
- MoE: ``p = softmax(x W_r)`` over all published experts; the
  ``num_experts_per_tok`` largest; weights ``p`` over the chosen, divided by
  their sum; ``y = sum_e w_e W_down(silu(W_gate x) * W_up x)``.

Departures forced by memory, none of them in the mathematics: attention takes
the queries in blocks of ``ATTENTION_ROWS`` against all the keys (the mask does
the rest), the loss takes the positions in blocks of ``LOSS_ROWS``, and every
block of either kind and every layer is recomputed in the backward pass
(``jax.checkpoint``), so that an 8,192-token row in float32 fits beside 9.5 GB
of state. The routed experts are computed the plain way: every held expert on
every token, weighted by the router's weight (nought where it did not choose
the expert); no sort, no gather.

``window_on`` false plants this architecture's own fault: the window left
off the sliding layers (every layer sees keys ``0 .. i``; each keeps its own
rotary embedding).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import ops

ATTENTION_ROWS = 256
LOSS_ROWS = 1024
SLIDING = "sliding_attention"


def _kernel(n_in: int, n_out: int) -> dict:
    return {"kernel": ((n_in, n_out), "normal")}


def _scale(n: int) -> dict:
    return {"scale": ((n,), "ones")}


def layer_kinds(s: dict) -> list:
    """The kind of each layer kept: ``layer_types`` is the period, as a
    comma-separated string (a configuration's shapes hold no lists)."""
    period = s["layer_types"].split(",")
    return [period[i % len(period)] for i in range(s["num_hidden_layers"])]


def param_spec(s: dict) -> dict:
    d, v = s["hidden_size"], s["vocab_size"]
    h, kv, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                 s["head_dim"])
    held, f = s["num_experts"], s["moe_intermediate_size"]
    # the embedding's rows are drawn N(0, 1) and every other matrix N(0,
    # 0.02): see ``make_params``
    spec = {"embed": {"embedding": ((v, d), "unit_normal")},
            "head": _kernel(d, v), "norm": _scale(d)}
    for i in range(s["num_hidden_layers"]):
        spec[f"layers_{i}"] = {
            "attn_norm": _scale(d), "ffn_norm": _scale(d),
            "attn": {"q": _kernel(d, h * hd), "k": _kernel(d, kv * hd),
                     "v": _kernel(d, kv * hd), "o": _kernel(h * hd, d),
                     "q_norm": _scale(hd), "k_norm": _scale(hd)},
            "moe": {"router_kernel": ((d, s["num_experts_published"]),
                                      "normal"),
                    "experts_gate": ((held, d, f), "normal"),
                    "experts_up": ((held, d, f), "normal"),
                    "experts_down": ((held, f, d), "normal")}}
    return spec


def make_params(spec: dict, seed: int):
    """Weights from the seed in one jitted call, float32: N(0, 0.02) matrices,
    unit norm scales, and an embedding of N(0, 1) rows. With the embedding at
    0.02 too, the attention's output (a mean of values over the keys, much the
    same vector for every query) outweighs a token's own embedding in the
    residual stream three to one, every token hands its router nearly the
    same input, and most tokens choose the same 8 experts: the rows sent to
    this chip's 16 experts then swing from 20,000 to 46,000 a layer with the
    seed and the rate with them (4.02 and 4.09 sequences a second on two
    seeds, each to 0.03 % on a second run; 3.999-4.004 on three seeds with
    this draw, 30,900-34,100 rows a layer: my chip runs, PR 34). A trained
    router is balanced; a draw that leaves it so at step 0 is the
    load-neutral one."""
    leaves, treedef = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[1], str))
    std = {"normal": 0.02, "unit_normal": 1.0}

    def build(key):
        return [jnp.ones(shape, jnp.float32) if kind == "ones" else
                std[kind] * jax.random.normal(jax.random.fold_in(key, i), shape,
                                              jnp.float32)
                for i, (shape, kind) in enumerate(leaves)]
    return jax.tree.unflatten(treedef, jax.jit(build)(jax.random.key(seed)))


def rms_norm(x, p, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * p["scale"]


def inv_freq(s: dict, kind: str):
    """(rotary frequencies of the head_dim / 2 pairs, the factor on cos and
    sin) of a layer kind. Sliding: ``e_i = theta^(-2i/d)``, factor 1. Full:
    YaRN: with ``c(b) = d ln(L / (2 pi b)) / (2 ln theta)``, ``low =
    floor(c(beta_fast))``, ``high = ceil(c(beta_slow))`` (kept inside 0 .. d -
    1) and ``ramp_i = clip((i - low) / (high - low), 0, 1)``, dimension ``i``
    turns at ``(e_i / factor) ramp_i + e_i (1 - ramp_i)``; the factor on cos
    and sin is ``yarn_attention_factor``."""
    d, theta = s["head_dim"], s["rope_theta"]
    e = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if kind == SLIDING:
        return jnp.asarray(e, jnp.float32), 1.0

    def c(b):
        return d * math.log(s["yarn_original_max_position_embeddings"]
                            / (2 * math.pi * b)) / (2 * math.log(theta))
    low = max(math.floor(c(s["yarn_beta_fast"])), 0)
    high = min(math.ceil(c(s["yarn_beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    scaled = (e / s["yarn_factor"]) * ramp + e * (1 - ramp)
    return jnp.asarray(scaled, jnp.float32), s["yarn_attention_factor"]


def rotary(x, freq, factor):
    """(..., n, r): positions 0..n-1, dimension i paired with i + r/2."""
    n, r = x.shape[-2], x.shape[-1]
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    a, b = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def masked_attention(q, k, v, window, mode):
    """softmax(q k^T / sqrt(d), key j visible to query i where j <= i and,
    with a window, j > i - window) v over (b, h, n, d), the queries in blocks
    of rows."""
    b, h, n, d = q.shape
    rows = ATTENTION_ROWS if n % ATTENTION_ROWS == 0 else n
    keys = jnp.arange(n)[None, :]

    @jax.checkpoint
    def block(xs):
        q_blk, lo = xs                                    # (b, h, rows, d)
        s = ops.einsum("bhqd,bhkd->bhqk", q_blk * d ** -0.5, k, mode)
        at = (lo + jnp.arange(rows))[:, None]
        mask = keys <= at
        if window is not None:
            mask = mask & (keys > at - window)
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        return ops.einsum("bhqk,bhkd->bhqd", p, v, mode)

    blocks = q.reshape(b, h, n // rows, rows, d).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(block, (blocks, jnp.arange(0, n, rows)))
    return out.transpose(1, 2, 0, 3, 4).reshape(b, h, n, d)


def attention(x, p, s, kind, mode, window_on=True):
    b, n, _ = x.shape
    h, kv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                s["head_dim"])
    eps = s["rms_norm_eps"]
    freq, factor = inv_freq(s, kind)

    def heads(name, count):
        return ops.dense(x, p[name], mode).reshape(b, n, count, d).transpose(
            0, 2, 1, 3)
    q = rotary(rms_norm(heads("q", h), p["q_norm"], eps), freq, factor)
    k = rotary(rms_norm(heads("k", kv), p["k_norm"], eps), freq, factor)
    v = heads("v", kv)
    # query head j reads key/value head j // (h / kv)
    k, v = (jnp.repeat(t, h // kv, axis=1) for t in (k, v))
    window = s["sliding_window"] if kind == SLIDING and window_on else None
    out = masked_attention(q, k, v, window, mode)
    return ops.dense(out.transpose(0, 2, 1, 3).reshape(b, n, h * d), p["o"],
                     mode)


def route(x, p, s):
    """(chosen experts (T, k), their weights (T, k)) over all the published
    experts, float32 whatever the mode: softmax over all of them, the k
    largest, their probabilities divided by their sum."""
    probs = jax.nn.softmax(
        ops.einsum("td,de->te", x, p["router_kernel"], "f32"), axis=-1)
    idx = jnp.argsort(-probs, axis=-1, stable=True)[
        :, : s["num_experts_per_tok"]]
    chosen = jnp.take_along_axis(probs, idx, axis=-1)
    return idx, chosen / jnp.sum(chosen, -1, keepdims=True)


def expert_layer(x, p, s, mode):
    """The held experts' part of the routed result (there is no shared
    expert), and the router's choice."""
    b, n, d = x.shape
    tokens = x.reshape(b * n, d)
    idx, weights = route(tokens, p, s)
    held = jnp.arange(s["num_experts"]) + s.get("first_expert", 0)
    # (held, T): the router's weight of each held expert, nought where the
    # token did not choose it
    w = jnp.sum(jnp.where(idx[None] == held[:, None, None], weights[None], 0.0),
                axis=-1)

    def one_expert(y, xs):
        w_e, gate, up, down = xs
        hidden = jax.nn.silu(ops.einsum("td,df->tf", tokens, gate, mode)) \
            * ops.einsum("td,df->tf", tokens, up, mode)
        return y + w_e[:, None] * ops.einsum("tf,fd->td", hidden, down,
                                             mode), None
    # a loop over the held experts, rolled (unrolled copies of three float32
    # products make a program of gigabytes at the cell's size)
    y, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(tokens),
                        (w, p["experts_gate"], p["experts_up"],
                         p["experts_down"]))
    return y.reshape(b, n, d), idx


def block(x, p, s, kind, mode, window_on=True):
    eps = s["rms_norm_eps"]
    h = x + attention(rms_norm(x, p["attn_norm"], eps), p["attn"], s, kind,
                      mode, window_on)
    y, idx = expert_layer(rms_norm(h, p["ffn_norm"], eps), p["moe"], s, mode)
    return h + y, idx


def hidden_states(params, tokens, s, mode, window_on=True, remat=True):
    """tokens (b, n) -> (the normed hidden states before the head, the
    routers' choices by layer)."""
    x = params["embed"]["embedding"][tokens]
    choices = {}
    for i, kind in enumerate(layer_kinds(s)):
        run = functools.partial(block, s=s, kind=kind, mode=mode,
                                window_on=window_on)
        x, choices[f"layers_{i}"] = (jax.checkpoint(run) if remat else run)(
            x, params[f"layers_{i}"])
    return rms_norm(x, params["norm"], s["rms_norm_eps"]), choices


def forward(params, tokens, s, mode, window_on=True, remat=True):
    """float32 logits (b, n, V) of token i + 1."""
    hidden, _ = hidden_states(params, tokens, s, mode, window_on, remat)
    return ops.dense(hidden, params["head"], mode)


def loss_sums(params, rows, s, mode, window_on=True):
    """rows (b, n + 1) of token ids -> [(the sum over all positions of the
    cross entropy of token i + 1, how many those are)]: one head. The
    logits live ``LOSS_ROWS`` positions at a time. ``window_on`` is what
    the harness hands every family as its fifth argument: false plants the
    architecture's own fault."""
    hidden, _ = hidden_states(params, rows[:, :-1], s, mode, window_on)
    d = hidden.shape[-1]
    hidden, targets = hidden.reshape(-1, d), rows[:, 1:].reshape(-1)
    size = LOSS_ROWS if hidden.shape[0] % LOSS_ROWS == 0 else hidden.shape[0]

    @jax.checkpoint
    def chunk(xs):
        h, t = xs
        logits = ops.dense(h, params["head"], mode)
        picked = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    sums = jax.lax.map(chunk, (hidden.reshape(-1, size, d),
                               targets.reshape(-1, size)))
    return [(jnp.sum(sums), targets.shape[0])]
