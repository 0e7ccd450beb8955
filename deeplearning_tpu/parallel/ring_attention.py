"""Ring attention: sequence-parallel exact attention over the ``seq`` axis.

A capability the reference does NOT have (SURVEY.md §2.9: SP/CP absent —
its only long-sequence tool is Swin's window locality). TPU-native design:
shard the sequence over the ``seq`` mesh axis; each device holds its Q/K/V
chunk; K/V chunks rotate around the ring via ``lax.ppermute`` (ICI
neighbor exchange) while each device accumulates its queries' attention
over every chunk with the same online-softmax update the flash kernel
uses. Peak memory per device is O(N/P · N/P) per block — exact attention
over sequences P× longer than one device could hold, with communication
hidden behind the per-chunk compute.

Composable: the per-chunk inner attention uses the Pallas flash kernel on
TPU (lax fallback elsewhere), so blockwise HBM savings and ring scaling
stack.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import SEQ_AXIS


NEG_INF = -1e30


def _chunk_attention_stats(q, k, v, sm_scale, kv_mask=None):
    """Un-normalized attention over one KV chunk: returns (numerator,
    max, sumexp) for online combining. q,k,v: (B, H, Nq, D)/(B, H, Nk, D).
    ``kv_mask`` (Nk,) bool marks valid key tokens — padded tokens (ring
    chunks must divide the global N, so wrappers zero-pad the tail) are
    excluded from the softmax."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if kv_mask is not None:
        s = jnp.where(kv_mask[None, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                  # (B,H,Nq)
    p = jnp.exp(s - m[..., None])
    if kv_mask is not None:
        p = p * kv_mask[None, None, None, :].astype(p.dtype)
    l = jnp.sum(p, axis=-1)
    num = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return num, m, l


def _combine(carry, update):
    """Online-softmax merge of (num, m, l) accumulators."""
    num1, m1, l1 = carry
    num2, m2, l2 = update
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    return (num1 * a1[..., None] + num2 * a2[..., None],
            m, l1 * a1 + l2 * a2)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str = SEQ_AXIS,
                   sm_scale: Optional[float] = None,
                   use_flash: bool = False,
                   kv_mask: Optional[jax.Array] = None) -> jax.Array:
    """Exact attention with K/V ring-rotated over ``axis_name``.

    Must run inside shard_map with ``axis_name`` bound; q/k/v are the
    device-local sequence chunks (B, H, Nlocal, D). Non-causal (the zoo's
    encoders are bidirectional).

    ``use_flash`` runs each chunk through the Pallas flash kernel
    (flash_attention_with_lse): a chunk's (out, lse) is an equivalent
    online-softmax accumulator (num=out, m=lse, l=1), so the ring merge
    is exact and never materializes a (Nlocal, Nlocal) score matrix in
    HBM. TRAINABLE: a custom VJP runs a second ring in the backward pass
    where each device computes per-chunk (dq, dk, dv) with the flash
    backward kernels against the GLOBAL logsumexp, rotating the dK/dV
    accumulators with the KV chunks (Liu & Abbeel ring attention bwd).
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if use_flash:
        if kv_mask is not None:
            raise NotImplementedError(
                "kv_mask needs the lax path (the flash kernel masks by "
                "static kv_len only) — pad to a seq-axis multiple "
                "instead, or set use_flash=False")
        return _ring_flash(axis_name, sm_scale, q, k, v)
    out, _ = _ring_forward(q, k, v, axis_name, sm_scale, use_flash=False,
                           kv_mask=kv_mask)
    return out


def _zero_like_varying(x, fill=0.0, drop_last=False):
    """A fill-valued f32 array DERIVED from ``x`` so it carries exactly
    x's varying-mesh-axes type — fori_loop requires carry init and body
    output types to match, and the body's accumulators inherit the
    inputs' axes (seq, and data when the batch dim is sharded)."""
    z = x.astype(jnp.float32)
    if drop_last:
        z = z[..., 0]
    return z * 0.0 + fill


def _ring_forward(q, k, v, axis_name, sm_scale, use_flash,
                  kv_mask=None):
    """Ring forward; returns (out, global_lse). ``kv_mask`` (Nlocal,)
    bool rotates around the ring with its KV chunk (lax path only)."""
    axis_size = jax.lax.axis_size(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def chunk_stats(q, kk, vv, mm):
        if use_flash:
            from ..ops.pallas.flash_attention import flash_attention_with_lse
            o, lse = flash_attention_with_lse(q, kk, vv, sm_scale=sm_scale)
            return (o.astype(jnp.float32), lse, jnp.ones_like(lse))
        return _chunk_attention_stats(q, kk, vv, sm_scale, kv_mask=mm)

    def body(i, state):
        carry, kk, vv, mm = state
        update = chunk_stats(q, kk, vv, mm)
        carry = _combine(carry, update)
        # rotate KV to the next device; last iteration's rotate is wasted
        # but keeps the loop body uniform (XLA overlaps it with compute).
        kk = jax.lax.ppermute(kk, axis_name, perm)
        vv = jax.lax.ppermute(vv, axis_name, perm)
        if mm is not None:
            mm = jax.lax.ppermute(mm, axis_name, perm)
        return carry, kk, vv, mm

    init = (_zero_like_varying(q),
            _zero_like_varying(q, fill=-jnp.inf, drop_last=True),
            _zero_like_varying(q, drop_last=True))
    (num, m, l), _, _, _ = jax.lax.fori_loop(
        0, axis_size, body, (init, k, v, kv_mask))
    l_safe = jnp.maximum(l, 1e-30)
    out = (num / l_safe[..., None]).astype(q.dtype)
    return out, m + jnp.log(l_safe)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ring_flash(axis_name, sm_scale, q, k, v):
    out, _ = _ring_flash_fwd(axis_name, sm_scale, q, k, v)
    return out


def _ring_flash_fwd(axis_name, sm_scale, q, k, v):
    out, lse = _ring_forward(q, k, v, axis_name, sm_scale, use_flash=True)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(axis_name, sm_scale, res, dout):
    """Backward ring: per-chunk flash gradients against the global LSE
    sum to the exact full-sequence gradient (flash_chunk_grads
    docstring), so dQ accumulates locally while (KV, dK, dV) rotate
    together — after a full circle the dK/dV accumulators are home with
    every device's contribution."""
    from ..ops.pallas.flash_attention import flash_chunk_grads

    q, k, v, out, lse = res
    axis_size = jax.lax.axis_size(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)

    def body(i, state):
        dq, kk, vv, dkk, dvv = state
        dq_c, dk_c, dv_c = flash_chunk_grads(q, kk, vv, dout, lse, delta,
                                             sm_scale=sm_scale)
        dq = dq + dq_c      # chunk grads are f32 (flash_chunk_grads)
        dkk = dkk + dk_c
        dvv = dvv + dv_c
        kk, vv, dkk, dvv = (jax.lax.ppermute(t, axis_name, perm)
                            for t in (kk, vv, dkk, dvv))
        return dq, kk, vv, dkk, dvv

    dq, _, _, dk, dv = jax.lax.fori_loop(
        0, axis_size, body,
        (_zero_like_varying(q), k, v,
         _zero_like_varying(k), _zero_like_varying(v)))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def make_ring_attention(mesh: Mesh, axis_name: str = SEQ_AXIS,
                        use_flash: bool = False):
    """shard_map-wrapped ring attention over a live mesh: takes globally
    sharded (B, H, N, D) arrays (sequence dim sharded over ``axis_name``)
    and returns the same sharding."""

    spec = P(None, None, axis_name, None)

    # pallas_call out_shapes carry no varying-mesh-axes info, so the
    # flash-backed path needs shard_map's vma check off
    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=not use_flash)
    def fn(q, k, v):
        return ring_attention(q, k, v, axis_name, use_flash=use_flash)

    return fn


def make_ring_attn_fn(mesh: Mesh, axis_name: str = SEQ_AXIS,
                      use_flash: bool = False):
    """Ring attention as a model ``attn_fn``: the (B, N, H, D) signature
    every transformer in the zoo accepts (vit.py Attention, transfg,
    mae). This is how sequence parallelism drops INTO a model instead of
    living beside it: build any ViT with
    ``attn_fn=make_ring_attn_fn(mesh)`` and its attention shards over
    the ``seq`` axis while the rest of the model stays GSPMD-sharded
    (batch over ``data``, sequence over ``seq``).

    Token counts rarely divide the seq axis (ViT-B/16 has 197 = 196+cls),
    so inputs are zero-padded to a multiple and a KV validity mask rides
    the ring with its chunk (lax path). ``use_flash=True`` requires the
    unpadded length to divide the axis exactly."""

    from ._seq_adapter import batch_axes, seq_attn_adapter

    axis_size = mesh.shape[axis_name]
    b_axes = batch_axes(mesh)

    rings = {}

    def _ring_for(shard_batch):
        if shard_batch not in rings:
            spec = P(b_axes if shard_batch else None, None, axis_name,
                     None)

            @functools.partial(
                jax.shard_map, mesh=mesh,
                in_specs=(spec, spec, spec, P(axis_name)),
                out_specs=spec, check_vma=not use_flash)
            def ring(q, k, v, mask):
                return ring_attention(
                    q, k, v, axis_name, use_flash=use_flash,
                    kv_mask=None if use_flash else mask)
            rings[shard_batch] = ring
        return rings[shard_batch]

    def call(qt, kt, vt, n, sharded):
        mask = jnp.arange(qt.shape[2]) < n
        return _ring_for(sharded)(qt, kt, vt, mask)

    return seq_attn_adapter(mesh, axis_size, axis_name, "ring",
                            use_flash, call)
