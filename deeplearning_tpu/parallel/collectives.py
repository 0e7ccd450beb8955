"""Collective helpers over the device mesh.

Maps the reference's torch.distributed usage (SURVEY.md §2.9) onto XLA
collectives: ``reduce_value`` all-reduce mean
(others/train_with_DDP/utils/distributed_utils.py:71) → ``pmean``;
metric ``reduce_dict`` (fasterRcnn utils/distributed_utils.py:116) →
tree-pmean; SyncBatchNorm (train.py:192) → batch-stat pmean inside the norm
(see ops/norm.py); object all_gather (YOLOX yolox/utils/dist.py:186) →
``process_allgather`` on host. Inside pjit-compiled code most collectives
are implicit — GSPMD inserts them from sharding constraints — so these
helpers are for shard_map code and for host-side gathers.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import DATA_AXIS, FSDP_AXIS


def pmean_tree(tree: Any, axis_name: str | tuple = (DATA_AXIS, FSDP_AXIS)) -> Any:
    """Mean a pytree across replicas — DDP's gradient/metric all-reduce.
    Only valid inside shard_map/pmap with the axis bound."""
    return jax.tree.map(lambda x: jax.lax.pmean(x, axis_name), tree)


def psum_tree(tree: Any, axis_name: str | tuple = (DATA_AXIS, FSDP_AXIS)) -> Any:
    return jax.tree.map(lambda x: jax.lax.psum(x, axis_name), tree)


# ---------------------------------------------------------------------------
# Quantized collectives (PAPERS.md "EQuARX: Efficient Quantized AllReduce
# in XLA"): block-scaled int8 payloads cut gradient all-reduce bytes ~4x.
# Each block of ``block`` consecutive elements shares one fp32 scale; the
# scale is rounded UP to a power of two so quantization is an exact
# binary shift whenever values (and their cross-replica sums) are small
# integers — that is what makes the parity test bitwise, and bounds the
# general-case error at s/2 <= max|x|/127 per element per stage.
# Two stages (quantize -> reduce-scatter -> requantize -> all-gather)
# mirror a ring all-reduce, so worst-case relative error is ~2/127 of the
# block max — fine for gradients, wrong for loss scalars; callers psum
# metrics in fp32.
# ---------------------------------------------------------------------------

_QMAX = 127.0
_TINY = 1e-30  # floor before log2 so all-zero blocks get scale 2^-~100


def _quantize_blocks(xb: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(..., block) fp32 -> int8 payload + per-block power-of-two scale."""
    maxabs = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    s = jnp.exp2(jnp.ceil(jnp.log2(jnp.maximum(maxabs, _TINY) / _QMAX)))
    q = jnp.clip(jnp.round(xb / s), -_QMAX, _QMAX).astype(jnp.int8)
    return q, s.astype(jnp.float32)


def _dequantize_blocks(q: jax.Array, s: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * s


def _pad_to(x: jax.Array, multiple: int) -> tuple[jax.Array, int]:
    pad = (-x.shape[-1]) % multiple
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x, pad


def _quantized_rs_stage(flat: jax.Array, axis_name: Any, n: int,
                        block: int) -> jax.Array:
    """Stage 1 of the quantized all-reduce, inside shard_map: every
    replica holds the SAME flat fp32 vector (length divisible by
    n*block); returns this replica's 1/n chunk of the cross-replica SUM.
    The wire carries int8 payloads + fp32 block scales via all_to_all
    (each replica ships peer-destined chunks), then the sum is done in
    fp32 after rescale — the EQuARX reduce-scatter stage."""
    chunks = flat.reshape(n, flat.shape[-1] // n)
    q, s = _quantize_blocks(chunks.reshape(n, -1, block))
    q = jax.lax.all_to_all(q, axis_name, 0, 0)
    s = jax.lax.all_to_all(s, axis_name, 0, 0)
    return jnp.sum(_dequantize_blocks(q, s), axis=0).reshape(-1)


def quantized_psum(x: jax.Array, axis_name: Any = (DATA_AXIS, FSDP_AXIS),
                   block: int = 256) -> jax.Array:
    """int8 block-scaled all-reduce SUM of ``x`` across ``axis_name``.
    Only valid inside shard_map with the axes bound; every replica must
    pass the same-shaped local array and gets the full summed array back
    (like ``jax.lax.psum``). Exact when per-replica values and their sums
    are integers within [-127, 127]; otherwise relative error is bounded
    by ~2/127 per block (two quantization stages)."""
    n = jax.lax.axis_size(axis_name)
    flat = x.astype(jnp.float32).reshape(-1)
    size = flat.shape[0]
    flat, _ = _pad_to(flat, n * block)
    part = _quantized_rs_stage(flat, axis_name, n, block)
    q2, s2 = _quantize_blocks(part.reshape(-1, block))
    q2 = jax.lax.all_gather(q2.reshape(-1), axis_name, axis=0, tiled=True)
    s2 = jax.lax.all_gather(s2.reshape(-1), axis_name, axis=0, tiled=True)
    out = _dequantize_blocks(q2.reshape(-1, block),
                             s2.reshape(-1, 1)).reshape(-1)
    return out[:size].reshape(x.shape).astype(x.dtype)


def quantized_psum_tree(tree: Any,
                        axis_name: Any = (DATA_AXIS, FSDP_AXIS),
                        block: int = 256) -> Any:
    """``psum_tree`` with int8 block-scaled payloads (EQuARX-style)."""
    return jax.tree.map(
        lambda x: quantized_psum(x, axis_name, block=block), tree)


def quantized_reduce_scatter(x: jax.Array,
                             axis_name: Any = (DATA_AXIS, FSDP_AXIS),
                             block: int = 256) -> jax.Array:
    """int8 reduce-scatter: every replica passes the same-shaped local
    array; returns this replica's ``x.shape[0]//n`` leading-dim slice of
    the cross-replica SUM (like ``jax.lax.psum_scatter(..., tiled=True)``).
    Requires ``x.shape[0] % n == 0`` — the ZeRO-1 grad path only routes
    leaves here when their zero1 spec shards dim 0. Skips the second
    quantization stage entirely (the scattered shard never rides the
    wire again), so only one stage of error applies."""
    n = jax.lax.axis_size(axis_name)
    if x.shape[0] % n != 0:
        raise ValueError(
            f"quantized_reduce_scatter needs dim0 % {n} == 0, "
            f"got shape {x.shape}")
    rows = x.shape[0] // n
    flat = x.astype(jnp.float32).reshape(n, -1)
    flat, pad = _pad_to(flat, block)
    part = _quantized_rs_stage(flat.reshape(-1), axis_name, n,
                               block)
    if pad:
        part = part[:-pad]
    return part.reshape((rows,) + x.shape[1:]).astype(x.dtype)


def host_allgather(tree: Any) -> Any:
    """Gather host-local (numpy-backed) pytrees from every process onto all
    hosts — the analog of torch.distributed all_gather of pickled objects
    (YOLOX dist.py:186, used for distributed COCO evaluation)."""
    from jax.experimental import multihost_utils
    if jax.process_count() == 1:
        return jax.tree.map(lambda x: jnp.asarray(x)[None], tree)
    return multihost_utils.process_allgather(tree)


def broadcast_from_host0(tree: Any) -> Any:
    """Rank-0 weight broadcast successor (others/train_with_DDP/
    train.py:163-177 did this with a tmp file + barrier)."""
    from jax.experimental import multihost_utils
    if jax.process_count() == 1:
        return tree
    return multihost_utils.broadcast_one_to_all(tree)


def sync_barrier(name: str = "barrier") -> None:
    from jax.experimental import multihost_utils
    if jax.process_count() > 1:
        multihost_utils.sync_global_devices(name)


def with_data_sharding_constraint(x: jax.Array, mesh: Optional[Mesh] = None
                                  ) -> jax.Array:
    """Pin the leading dim of an intermediate to the data axes inside jit —
    the steering wheel for GSPMD when propagation is ambiguous."""
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(
            mesh or _current_mesh(), P((DATA_AXIS, FSDP_AXIS))))


def _current_mesh() -> Mesh:
    env = jax.sharding.get_abstract_mesh()
    if env is None:
        raise RuntimeError("No mesh in scope; pass mesh= explicitly")
    return env
