"""Mamba-2's state-space mixer core (state-space duality, Dao & Gu 2024) in
``jax.numpy``: the chunked scan, the causal depthwise convolution before it
and the gated RMSNorm after it. The lax path, the CPU path and the oracle of
any later kernel; nothing here chooses a path.

With ``x`` (B, N, H, P) the heads' inputs, ``dt`` (B, N, H) the step sizes
``Δ`` (after the softplus), ``a`` (H,) the negative rates ``A``, and ``b``,
``c`` (B, N, G, S) shared by the ``H / G`` heads of a group, head ``h`` of
group ``h // (H / G)``:

    S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t ⊗ B_t,   S_{-1} = 0
    y_t = S_t C_t

a (P, S) state a head. ``ssd_scan`` computes it a chunk of ``chunk``
positions at a time: within a chunk, the decays between two positions are a
segment sum of ``Δ A`` and the output is the lower triangle ``(L ∘ C Bᵀ)
(Δ x)``; each chunk's final state is a weighted sum of its inputs; a
``lax.scan`` over the chunks carries the state from one to the next; and the
state a chunk starts from adds ``C_t`` times it, decayed to ``t``. Decays and
states are float32; the products take ``x``'s dtype with float32
accumulation. The gradient is autodiff's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def causal_conv1d(x: jax.Array, kernel: jax.Array, bias: jax.Array
                  ) -> jax.Array:
    """Depthwise causal convolution over positions, float32: x (B, N, C),
    kernel (K, C), bias (C,); ``y_t = bias + sum_i kernel[i] x_{t - K + 1 +
    i}`` with zeros before position 0 (PyTorch's ``conv1d`` with K - 1
    positions of padding, its first N outputs)."""
    k, n = kernel.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    return bias + sum(xp[:, i:i + n] * kernel[i] for i in range(k))


def gated_rms_norm(y: jax.Array, z: jax.Array, scale: jax.Array,
                   eps: float) -> jax.Array:
    """``RMSNorm(y ⊙ silu(z)) * scale`` over the last axis, float32."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
    return g * jax.lax.rsqrt(var + eps) * scale


def _chunks(t: jax.Array, chunk: int) -> jax.Array:
    """(B, N, ...) -> (B, N / chunk, chunk, ...)."""
    return t.reshape(t.shape[0], t.shape[1] // chunk, chunk, *t.shape[2:])


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, chunk: int) -> jax.Array:
    """y (B, N, H, P) in x's dtype of the recurrence in the module
    docstring, without the skip ``D x``. N need not be a multiple of
    ``chunk``: the tail is padded with zeros, which no earlier position
    sees."""
    with jax.named_scope("ssd_scan"):
        bsz, n, h, p = x.shape
        g, s = b.shape[-2:]
        k = h // g                               # heads a group
        pad = -n % chunk
        if pad:
            x, dt, b, c = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)]
                                   * (t.ndim - 2)) for t in (x, dt, b, c))
        dt = dt.astype(jnp.float32)
        # (B, Z chunks, L, G, K, P) and (B, Z, L, G, S)
        xdt = _chunks((x.astype(jnp.float32) * dt[..., None]).astype(x.dtype),
                      chunk).reshape(bsz, -1, chunk, g, k, p)
        bc, cc = _chunks(b, chunk), _chunks(c, chunk)
        # the decays, float32: cum[..., l] = sum of Δ A over the chunk's
        # positions 0 .. l, (B, Z, G, K, L)
        da = _chunks(dt * a.astype(jnp.float32), chunk)
        cum = jnp.cumsum(da.reshape(bsz, -1, chunk, g, k)
                         .transpose(0, 1, 3, 4, 2), axis=-1)
        # within a chunk: position l sees m <= l through exp(cum_l - cum_m)
        tril = jnp.tril(jnp.ones((chunk, chunk), bool))
        seg = jnp.exp(jnp.where(tril, cum[..., :, None] - cum[..., None, :],
                                -jnp.inf))
        scores = jnp.einsum("bzlgs,bzmgs->bzglm", cc, bc,
                            preferred_element_type=jnp.float32)
        y = jnp.einsum("bzgklm,bzmgkp->bzlgkp",
                       (seg * scores[:, :, :, None]).astype(x.dtype), xdt,
                       preferred_element_type=jnp.float32)
        # each chunk's final state from its own inputs, (B, Z, G, K, P, S)
        to_end = jnp.exp(cum[..., -1:] - cum).transpose(0, 1, 4, 2, 3)
        states = jnp.einsum(
            "bzmgs,bzmgkp->bzgkps", bc,
            (xdt.astype(jnp.float32) * to_end[..., None]).astype(x.dtype),
            preferred_element_type=jnp.float32)

        # the state each chunk starts from, carried chunk to chunk
        def carry(state, inputs):
            own, decay = inputs
            return state * decay[..., None, None] + own, state
        _, starts = jax.lax.scan(
            carry, jnp.zeros((bsz, g, k, p, s), jnp.float32),
            (jnp.moveaxis(states, 1, 0),
             jnp.moveaxis(jnp.exp(cum[..., -1]), 1, 0)))
        starts = jnp.moveaxis(starts, 0, 1)
        y = y + jnp.einsum("bzlgs,bzgkps->bzlgkp", cc.astype(jnp.float32),
                           starts) \
            * jnp.exp(cum).transpose(0, 1, 4, 2, 3)[..., None]
        return y.reshape(bsz, -1, h, p)[:, :n].astype(x.dtype)
