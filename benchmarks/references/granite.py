"""Plain reference of ``granite`` (Granite 4.0-H Micro,
https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json,
``model_type`` granitemoehybrid without experts): a causal pre-norm decoder of
Mamba-2 state-space layers with a full attention layer among every ten, no
positional embedding, a dense SwiGLU MLP in every layer, a tied head and μP
multipliers. Straight ``jax.numpy``, float32, every matrix product at
``highest`` (``ops.einsum``), no kernels, no cache; the same share of a
stated deployment as the program is given: ``vocab_size`` rows of the tied
vocabulary. Parameter names are the program's, so one tree serves both.
Imports nothing of the program.

With ``x`` a token's hidden state, RMSNorm at ``rms_norm_eps``, no biases but
the convolution's:

- model: ``h₀ = embedding_multiplier · Embed(tokens)``; the blocks; a final
  RMSNorm; ``logits = h Embedᵀ / logits_scaling``;
- block: ``h = x + r · Mixer(RMSNorm(x))``, ``out = h + r · MLP(RMSNorm(h))``
  with ``r`` the residual multiplier; ``MLP(u) = W_out(silu(a) ⊙ b)``, ``[a,
  b] = W_in u``;
- Mamba mixer: ``[z, xBC, dt] = u W_in``; ``xBC ← silu(bias + Σ_i w_i ·
  xBC_{t-3+i})`` (zeros before position 0); ``[x, B, C] = xBC``, ``x`` in
  heads; ``Δ = softplus(dt + dt_bias)``, ``A = −exp(A_log)``; per head, one
  position at a time, ``S_t = exp(Δ_t A) S_{t−1} + Δ_t x_t ⊗ B_t`` from
  ``S = 0`` and ``y_t = S_t C_t + D x_t``, B and C those of the head's group;
  ``y ← RMSNorm_w(y ⊙ silu(z))`` over all of ``y``; ``out = y W_out``;
- attention mixer: ``q, k, v = u W_q, u W_k, u W_v`` in heads of ``hidden /
  heads``, K and V repeated so that query head ``j`` reads key/value head ``j
  // (heads / kv heads)``; no positional embedding; scores
  ``attention_multiplier · q·k``, key ``j`` visible to query ``i`` where ``j
  <= i``, the mask a comparison of positions; softmax; ``W_o``.

Departures forced by memory, none of them in the mathematics: a layer is
computed ``SEGMENT`` positions at a time (a Mamba layer carries the
convolution's last inputs and the recurrence's state from one segment to the
next; an attention layer makes the keys and values of the whole row first),
each segment recomputed for the gradient; within a segment the recurrence
keeps its state every ``STRETCH`` positions and recomputes each stretch,
attention takes the queries in blocks of ``ATTENTION_ROWS`` against all the
keys; the loss takes the positions in blocks of ``LOSS_ROWS``; and every
layer is recomputed in the backward pass. So an 8,192-token row in float32
fits beside 12.35 GB of state and the 3.1 GB of its gradient.

``carried`` false plants this architecture's own fault: the state not carried
from one chunk of ``mamba_chunk_size`` positions to the next (it starts from
zero at every multiple of the chunk), the bug of a chunked scan that drops its
pass between chunks.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import ops

SEGMENT = 1024
STRETCH = 64
ATTENTION_ROWS = 128
LOSS_ROWS = 1024
MAMBA = "mamba"
# the embedding's draw: ``embedding_multiplier`` (12) times rows of N(0, 1/12)
# enter the first block at RMS 1 (see ``make_params``)
EMBEDDING_STD = 1.0 / 12.0


def layer_kinds(s: dict) -> list:
    """The kind of each layer kept: ``layer_types`` is the period, as a
    comma-separated string (a configuration's shapes hold no lists)."""
    period = s["layer_types"].split(",")
    return [period[i % len(period)] for i in range(s["num_hidden_layers"])]


def _kernel(n_in: int, n_out: int) -> dict:
    return {"kernel": ((n_in, n_out), "normal")}


def _scale(n: int) -> dict:
    return {"scale": ((n,), "ones")}


def param_spec(s: dict) -> dict:
    d, v, f = s["hidden_size"], s["vocab_size"], s["intermediate_size"]
    h, kv = s["num_attention_heads"], s["num_key_value_heads"]
    hd = d // h
    heads, inner = s["mamba_n_heads"], s["mamba_n_heads"] * s["mamba_d_head"]
    conv = inner + 2 * s["mamba_n_groups"] * s["mamba_d_state"]
    spec = {"embed": {"embedding": ((v, d), "embedding")}, "norm": _scale(d)}
    for i, kind in enumerate(layer_kinds(s)):
        layer = {"mixer_norm": _scale(d), "ffn_norm": _scale(d),
                 "input_linear": _kernel(d, 2 * f),
                 "output_linear": _kernel(f, d)}
        if kind == MAMBA:
            layer["mixer"] = {
                "in_proj": _kernel(d, inner + conv + heads),
                "conv_kernel": ((s["mamba_d_conv"], conv), "conv"),
                "conv_bias": ((conv,), "conv"),
                "dt_bias": ((heads,), "dt_bias"), "A_log": ((heads,), "a_log"),
                "D": ((heads,), "ones"), "norm_scale": ((inner,), "ones"),
                "out_proj": _kernel(inner, d)}
        else:
            layer["attn"] = {"q": _kernel(d, h * hd), "k": _kernel(d, kv * hd),
                             "v": _kernel(d, kv * hd), "o": _kernel(h * hd, d)}
        spec[f"layers_{i}"] = layer
    return spec


def make_params(spec: dict, seed: int):
    """Weights from the seed in one jitted call, float32: N(0, 0.02) matrices,
    unit norm scales and ``D``, and Mamba-2's own draw of the rest: ``A``
    uniform in [1, 16] (kept as ``A_log``), ``Δ`` log-uniform in [0.001, 0.1]
    (kept as ``dt_bias`` through the inverse softplus), the convolution's
    kernel and bias uniform in ±1/sqrt(d_conv) (PyTorch's default for a
    convolution: at N(0, 0.02) its output, and so x, B and C, would be some
    0.02 and the recurrence a ten-thousandth of the skip ``D x``, invisible
    to any comparison); and an embedding of N(0, 1/12) rows. The embedding is the head too: with the
    multiplier of 12 a token enters the first block at RMS 1, each block adds
    0.22 times a mixer and an MLP whose outputs are of order one, and the
    logits ``h Embedᵀ / 8`` have a spread of 0.47 (ln 12,544 = 9.44 plus a
    little for the loss); at N(0, 0.02) the residual stream would start at
    RMS 0.24 under blocks of order one."""
    leaves, treedef = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[1], str))

    def draw(key, shape, kind, conv_fan_in):
        if kind == "ones":
            return jnp.ones(shape, jnp.float32)
        if kind == "a_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              1.0, 16.0))
        if kind == "dt_bias":
            dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                            math.log(1e-3), math.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        if kind == "conv":
            bound = conv_fan_in ** -0.5
            return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
        std = {"normal": 0.02, "embedding": EMBEDDING_STD}[kind]
        return std * jax.random.normal(key, shape, jnp.float32)

    # the depthwise convolution's fan-in is its width, the first axis of its
    # kernel
    fan_in = next((shape[0] for shape, kind in leaves if kind == "conv"
                   and len(shape) == 2), 1)

    def build(key):
        return [draw(jax.random.fold_in(key, i), shape, kind, fan_in)
                for i, (shape, kind) in enumerate(leaves)]
    return jax.tree.unflatten(treedef, jax.jit(build)(jax.random.key(seed)))


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _by_rows(t, rows: int):
    """(b, n, ...) -> (n / rows, b, rows, ...): a scan's sequence of
    segments."""
    b, n = t.shape[:2]
    return jnp.moveaxis(t.reshape(b, n // rows, rows, *t.shape[2:]), 1, 0)


def _joined(t):
    """The inverse of ``_by_rows``."""
    t = jnp.moveaxis(t, 0, 1)
    return t.reshape(t.shape[0], -1, *t.shape[3:])


def recurrence(x, delta, a, b, c, state, start, s, mode, carried=True):
    """(y (b, n, h, p), the state after the last position) of ``S_t = exp(Δ_t
    A) S_{t-1} + Δ_t x_t ⊗ B_t``, ``y_t = S_t C_t``, one position at a time,
    from ``state`` (b, g, h / g, p, s) at position ``start``; x (b, n, h, p),
    delta (b, n, h), a (h,), b, c (b, n, g, s), head ``h`` of group ``h // (h
    / g)``. The state is kept every ``STRETCH`` positions and each stretch
    recomputed for the gradient. ``carried`` false starts the state from zero
    at every multiple of ``mamba_chunk_size``."""
    bsz, n, h, p = x.shape
    g = b.shape[2]
    chunk = s["mamba_chunk_size"]
    a = a.reshape(g, h // g)

    def position(state, xs):
        x_t, d_t, b_t, c_t, t = xs       # (b, g, k, p), (b, g, k), (b, g, s)
        if not carried:
            state = jnp.where(t % chunk == 0, 0.0, state)
        state = jnp.exp(d_t * a)[..., None, None] * state \
            + (d_t[..., None] * x_t)[..., None] * b_t[:, :, None, None, :]
        return state, ops.einsum("bgkps,bgs->bgkp", state, c_t, mode)

    @jax.checkpoint
    def stretch(state, xs):
        return jax.lax.scan(position, state, xs)

    rows = STRETCH if n % STRETCH == 0 else n
    xs = tuple(jnp.moveaxis(_by_rows(t, rows), 2, 1) for t in (
        x.reshape(bsz, n, g, h // g, p), delta.reshape(bsz, n, g, h // g), b,
        c)) + ((start + jnp.arange(n)).reshape(n // rows, rows),)
    state, y = jax.lax.scan(stretch, state, xs)
    # y: (n / rows, rows, b, g, k, p)
    y = jnp.moveaxis(y.reshape(n, bsz, h, p), 0, 1)
    return y, state


def short_conv(x, kernel, bias):
    """``bias + Σ_i kernel[i] x_{t - K + 1 + i}``, zeros before position 0:
    a sum of K shifted products."""
    k, n = kernel.shape[0], x.shape[1]
    out = bias
    for i in range(k):
        shift = k - 1 - i
        shifted = jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, :n]
        out = out + shifted * kernel[i]
    return out


def mlp(h, p, s, mode):
    """``h + r · W_out(silu(a) ⊙ b)``, ``[a, b] = W_in RMSNorm(h)``."""
    f = s["intermediate_size"]
    ab = ops.dense(rms_norm(h, p["ffn_norm"]["scale"], s["rms_norm_eps"]),
                   p["input_linear"], mode)
    return h + s["residual_multiplier"] * ops.dense(
        jax.nn.silu(ab[..., :f]) * ab[..., f:], p["output_linear"], mode)


def mamba_block(x, p, s, mode, carried=True):
    """A Mamba layer, ``SEGMENT`` positions at a time: the convolution's last
    K - 1 inputs and the recurrence's state are carried from one segment to
    the next."""
    bsz, n, _ = x.shape
    m, eps = p["mixer"], s["rms_norm_eps"]
    heads, hd = s["mamba_n_heads"], s["mamba_d_head"]
    g, st, k = s["mamba_n_groups"], s["mamba_d_state"], s["mamba_d_conv"]
    inner = heads * hd
    conv = inner + 2 * g * st
    rows = SEGMENT if n % SEGMENT == 0 else n

    @jax.checkpoint
    def segment(carry, xs):
        tail, state = carry
        x_seg, start = xs
        zxbcdt = ops.dense(rms_norm(x_seg, p["mixer_norm"]["scale"], eps),
                           m["in_proj"], mode)
        z, dt = zxbcdt[..., :inner], zxbcdt[..., inner + conv:]
        joined = jnp.concatenate([tail, zxbcdt[..., inner:inner + conv]], 1)
        xbc = jax.nn.silu(short_conv(joined, m["conv_kernel"],
                                     m["conv_bias"])[:, k - 1:])
        xh = xbc[..., :inner].reshape(bsz, rows, heads, hd)
        y, state = recurrence(
            xh, jax.nn.softplus(dt + m["dt_bias"]), -jnp.exp(m["A_log"]),
            xbc[..., inner:inner + g * st].reshape(bsz, rows, g, st),
            xbc[..., inner + g * st:].reshape(bsz, rows, g, st), state, start,
            s, mode, carried)
        y = (y + m["D"][:, None] * xh).reshape(bsz, rows, inner)
        y = rms_norm(y * jax.nn.silu(z), m["norm_scale"], eps)
        h = x_seg + s["residual_multiplier"] * ops.dense(y, m["out_proj"],
                                                         mode)
        return (joined[:, rows:], state), mlp(h, p, s, mode)

    carry = (jnp.zeros((bsz, k - 1, conv), jnp.float32),
             jnp.zeros((bsz, g, heads // g, hd, st), jnp.float32))
    _, out = jax.lax.scan(segment, carry,
                          (_by_rows(x, rows), jnp.arange(0, n, rows)))
    return _joined(out)


def masked_attention(q, k, v, scale, start, mode):
    """softmax(scale q k^T, key j visible to query i where j <= i) v: q (b,
    h, rows, d) of positions ``start ..``, k, v (b, h, n, d), the queries in
    blocks of ``ATTENTION_ROWS``."""
    b, h, n, d = q.shape
    rows = ATTENTION_ROWS if n % ATTENTION_ROWS == 0 else n
    keys = jnp.arange(k.shape[2])[None, :]

    @jax.checkpoint
    def block(xs):
        q_blk, lo = xs                                    # (b, h, rows, d)
        scores = ops.einsum("bhqd,bhkd->bhqk", q_blk * scale, k, mode)
        mask = keys <= (lo + jnp.arange(rows))[:, None]
        p = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        return ops.einsum("bhqk,bhkd->bhqd", p, v, mode)

    blocks = q.reshape(b, h, n // rows, rows, d).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(block, (blocks, start + jnp.arange(0, n, rows)))
    return out.transpose(1, 2, 0, 3, 4).reshape(b, h, n, d)


def attention_block(x, p, s, mode):
    """An attention layer: keys and values of the whole row first, then the
    queries, the output and the MLP ``SEGMENT`` positions at a time."""
    b, n, d_model = x.shape
    h, kv = s["num_attention_heads"], s["num_key_value_heads"]
    d, a = d_model // h, p["attn"]
    norm = functools.partial(rms_norm, scale=p["mixer_norm"]["scale"],
                             eps=s["rms_norm_eps"])
    rows = SEGMENT if n % SEGMENT == 0 else n

    def heads(u, name, count):
        return ops.dense(u, a[name], mode).reshape(b, -1, count, d).transpose(
            0, 2, 1, 3)
    # query head j reads key/value head j // (h / kv)
    k, v = (jnp.repeat(heads(norm(x), name, kv), h // kv, axis=1)
            for name in "kv")

    @jax.checkpoint
    def segment(_, xs):
        x_seg, start = xs
        out = masked_attention(heads(norm(x_seg), "q", h), k, v,
                               s["attention_multiplier"], start, mode)
        y = ops.dense(out.transpose(0, 2, 1, 3).reshape(b, rows, h * d),
                      a["o"], mode)
        return None, mlp(x_seg + s["residual_multiplier"] * y, p, s, mode)

    _, out = jax.lax.scan(segment, None,
                          (_by_rows(x, rows), jnp.arange(0, n, rows)))
    return _joined(out)


def block(x, p, s, kind, mode, carried=True):
    if kind == MAMBA:
        return mamba_block(x, p, s, mode, carried)
    return attention_block(x, p, s, mode)


def hidden_states(params, tokens, s, mode, carried=True, remat=True):
    """tokens (b, n) -> the normed hidden states before the head."""
    x = s["embedding_multiplier"] * params["embed"]["embedding"][tokens]
    for i, kind in enumerate(layer_kinds(s)):
        run = functools.partial(block, s=s, kind=kind, mode=mode,
                                carried=carried)
        x = (jax.checkpoint(run) if remat else run)(x, params[f"layers_{i}"])
    return rms_norm(x, params["norm"]["scale"], s["rms_norm_eps"])


def _logits(hidden, params, s, mode):
    return ops.einsum("...d,vd->...v", hidden, params["embed"]["embedding"],
                      mode) / s["logits_scaling"]


def forward(params, tokens, s, mode, carried=True, remat=True):
    """float32 logits (b, n, V) of token i + 1."""
    return _logits(hidden_states(params, tokens, s, mode, carried, remat),
                   params, s, mode)


def loss_sums(params, rows, s, mode, carried=True):
    """rows (b, n + 1) of token ids -> [(the sum over all positions of the
    cross entropy of token i + 1, how many those are)]: one head. The
    logits live ``LOSS_ROWS`` positions at a time. ``carried`` is what the
    harness hands every family as its fifth argument: false plants the
    architecture's own fault."""
    hidden = hidden_states(params, rows[:, :-1], s, mode, carried)
    d = hidden.shape[-1]
    hidden, targets = hidden.reshape(-1, d), rows[:, 1:].reshape(-1)
    size = LOSS_ROWS if hidden.shape[0] % LOSS_ROWS == 0 else hidden.shape[0]

    @jax.checkpoint
    def chunk(xs):
        h, t = xs
        logits = _logits(h, params, s, mode)
        picked = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked)

    sums = jax.lax.map(chunk, (hidden.reshape(-1, size, d),
                               targets.reshape(-1, size)))
    return [(jnp.sum(sums), targets.shape[0])]

