"""Ulysses sequence parallelism: all-to-all head redistribution.

The second SP flavor next to ring attention (the task's "ring attention
or all-to-all sequence/context parallelism"; neither exists in the
reference — SURVEY.md §2.9 lists SP/CP as absent). Where ring attention
rotates KV chunks P times around the ``seq`` axis, Ulysses does ONE
``lax.all_to_all`` that trades the sharded sequence dimension for a
sharded head dimension: each device then holds the FULL sequence for
H/P heads, runs any off-the-shelf attention (including the Pallas flash
kernel — and unlike the ring+flash path this stays differentiable,
since all_to_all has a transpose and the inner attention is a normal
trainable op), and a second all_to_all restores sequence sharding.

Communication: 2 all-to-alls of the activations per call (O(B·N·D·H/P)
bytes each over ICI) vs ring's P ppermutes of K/V — Ulysses wins when
heads divide the axis and N is large; ring wins when H < P or ICI
bandwidth must overlap per-chunk compute.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import SEQ_AXIS


def _default_attention(q, k, v, sm_scale, valid_len=None):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if valid_len is not None and valid_len < k.shape[2]:
        col = jnp.arange(k.shape[2])
        s = jnp.where(col[None, None, None, :] < valid_len, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis_name: str = SEQ_AXIS,
                      sm_scale: Optional[float] = None,
                      attn_fn: Optional[Callable] = None,
                      valid_len: Optional[int] = None) -> jax.Array:
    """Must run inside shard_map with ``axis_name`` bound; q/k/v are the
    device-local sequence chunks (B, H, N/P, D) with H divisible by the
    axis size. ``attn_fn`` sees (B, H/P, N, D) full-sequence blocks
    (default: softmax attention; pass the Pallas flash kernel for fused
    long-context blocks). If it accepts an ``sm_scale`` keyword the
    scale is forwarded; plain ``attn_fn(q, k, v)`` callables are allowed
    only with the default scale."""
    p_size = jax.lax.axis_size(axis_name)
    b, h, nl, d = q.shape
    if h % p_size:
        raise ValueError(f"heads={h} must divide over axis size {p_size}")
    if sm_scale is None:
        sm_scale = d ** -0.5

    # seq-sharded -> head-sharded: split heads, gather sequence
    def scatter_heads(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

    def gather_heads(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    if attn_fn is not None and valid_len is not None \
            and valid_len < nl * p_size:
        raise ValueError(
            "valid_len masking is only implemented for the default inner "
            "attention — a custom attn_fn would silently attend padded "
            "keys. Pad N to a multiple of the axis instead.")
    qh, kh, vh = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    if attn_fn is None:
        # the gathered sequence carries any zero-padding at its global
        # tail, so a STATIC valid_len bound masks it exactly
        out = _default_attention(qh, kh, vh, sm_scale, valid_len=valid_len)
    else:
        # forward sm_scale when the fn accepts it (flash_attention does)
        # so an explicit scale is never silently dropped; plain
        # attn_fn(q, k, v) callables still work with the default scale
        import inspect
        try:
            takes_scale = "sm_scale" in inspect.signature(
                attn_fn).parameters
        except (TypeError, ValueError):
            takes_scale = False
        if not takes_scale and sm_scale != q.shape[-1] ** -0.5:
            raise ValueError(
                "explicit sm_scale given but attn_fn does not accept an "
                "sm_scale keyword — it would be silently ignored")
        out = (attn_fn(qh, kh, vh, sm_scale=sm_scale) if takes_scale
               else attn_fn(qh, kh, vh))
    return gather_heads(out.astype(q.dtype))


def make_ulysses_attention(mesh: Mesh, axis_name: str = SEQ_AXIS,
                           attn_fn: Optional[Callable] = None,
                           check_vma: bool = True):
    """shard_map-wrapped Ulysses attention: takes globally sharded
    (B, H, N, D) arrays (sequence sharded over ``axis_name``) and returns
    the same sharding. Set check_vma=False when attn_fn is a pallas_call
    (its out_shapes carry no varying-mesh-axes info)."""

    spec = P(None, None, axis_name, None)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec, check_vma=check_vma)
    def fn(q, k, v):
        return ulysses_attention(q, k, v, axis_name, attn_fn=attn_fn)

    return fn


def make_ulysses_attn_fn(mesh: Mesh, axis_name: str = SEQ_AXIS,
                         use_flash: bool = False):
    """Ulysses as a model ``attn_fn`` — the (B, N, H, D) signature every
    transformer in the zoo accepts (same drop-in contract as
    ring_attention.make_ring_attn_fn). Token counts that don't divide
    the ``seq`` axis are zero-padded; padding lands at the gathered
    sequence's tail, so the inner attention masks it with a static
    bound. ``use_flash=True`` runs each head block through the Pallas
    flash kernel and requires N to divide the axis exactly."""

    from ._seq_adapter import batch_axes, seq_attn_adapter

    axis_size = mesh.shape[axis_name]
    b_axes = batch_axes(mesh)

    inner = None
    if use_flash:
        from ..ops.pallas.flash_attention import flash_attention
        inner = flash_attention

    # one shard_map per (token count, batch-sharded?) — shared by every
    # layer of a model; Ulysses' valid_len is static per shape
    _fns = {}

    def call(qt, kt, vt, n, sharded):
        key = (n, sharded)
        if key not in _fns:
            spec = P(b_axes if sharded else None, None, axis_name, None)

            @functools.partial(
                jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
                out_specs=spec, check_vma=not use_flash)
            def fn(q, k, v):
                return ulysses_attention(q, k, v, axis_name,
                                         attn_fn=inner, valid_len=n)
            _fns[key] = fn
        return _fns[key](qt, kt, vt)

    return seq_attn_adapter(mesh, axis_size, axis_name, "ulysses",
                            use_flash, call)
