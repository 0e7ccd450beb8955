"""Pipeline parallelism: GPipe-style microbatch pipeline over a mesh axis.

A capability beyond the reference (SURVEY.md §2.9: PP absent). Design:
stage parameters carry a leading S (stage) axis sharded over the ``pipe``
mesh axis; the schedule runs inside shard_map — each device applies its
stage to its current microbatch then ppermutes activations to the next
device. With M microbatches and S stages the loop runs S+M-1 ticks
(bubble fraction (S-1)/(S+M-1)), all under one jit.

The stage function must be shape-preserving (same activation shape in and
out, the usual transformer-block setting), which keeps the rotating
buffer static-shaped.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PIPE_AXIS = "model"     # reuse the model axis for stages by default


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,           # pytree with leading S axis on leaves
    x: jax.Array,                # (M, micro_batch, ...) microbatches
    mesh: Mesh,
    axis_name: str = PIPE_AXIS,
) -> jax.Array:
    """Run x through S pipelined stages; returns (M, micro_batch, ...).

    stage_fn(params_slice, activation) -> activation, applied by every
    device to the microbatch currently resident on it.
    """
    return _pipeline_schedule(stage_fn, stage_params, x, mesh, axis_name)


def _pipeline_schedule(
    apply_stage: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,           # pytree with leading S axis on leaves
    x: jax.Array,                # (M, micro_batch, ...) microbatches
    mesh: Mesh,
    axis_name: str,
) -> jax.Array:
    """The shared GPipe fill-drain schedule: apply_stage runs on each
    device with its de-stacked param slice and the resident microbatch,
    then activations ppermute one stage forward."""
    s = mesh.shape[axis_name]
    m = x.shape[0]
    if m % s != 0:
        raise ValueError(
            f"microbatches ({m}) must be divisible by pipeline stages "
            f"({s}): the (M,...) input is sharded P({axis_name!r}) for "
            "storage, so a non-multiple silently truncates outputs")

    param_specs = jax.tree.map(lambda _: P(axis_name), stage_params)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(param_specs, P(axis_name)),
        out_specs=P(axis_name))
    def run(params, xs):
        idx = jax.lax.axis_index(axis_name)
        # params: leaves (1, ...) — this device's stage; xs
        # (ceil(M/S), ...) microbatches sharded over the axis for
        # storage; gather to a local queue (M is small; activations
        # are microbatch-sized)
        params = jax.tree.map(lambda p: p[0], params)
        all_x = jax.lax.all_gather(xs, axis_name, tiled=True)
        n_ticks = s + m - 1
        perm = [(i, (i + 1) % s) for i in range(s)]

        def tick(carry, t):
            buf, outputs = carry
            # stage 0 ingests microbatch t (if any) — other stages use buf
            feed = jnp.where(t < m, t, 0)
            incoming = jnp.where(idx == 0, 1.0, 0.0)
            inject = all_x[feed] * incoming + buf * (1 - incoming)
            y = apply_stage(params, inject)
            # device s-1's output at tick t is microbatch t-(s-1)
            out_slot = t - (s - 1)
            is_last = idx == s - 1
            valid = (out_slot >= 0) & (out_slot < m) & is_last
            # select, not lax.cond: both arms always run (the update is
            # microbatch-sized, so this costs nothing)
            updated = jax.lax.dynamic_update_index_in_dim(
                outputs, y, jnp.maximum(out_slot, 0), 0)
            outputs = jnp.where(valid, updated, outputs)
            # rotate activations forward one stage
            buf = jax.lax.ppermute(y, axis_name, perm)
            return (buf, outputs), None

        buf0 = jnp.zeros_like(all_x[0])
        outputs0 = jnp.zeros_like(all_x)
        # scan, not fori_loop: the trip count is static and scan is
        # reverse-mode differentiable, so the SAME schedule serves the
        # training step (grads flow back through ppermute/psum)
        (_, outputs), _ = jax.lax.scan(
            tick, (buf0, outputs0), jnp.arange(n_ticks))
        # outputs live on the last stage; share them back to all devices
        outputs = jax.lax.psum(outputs, axis_name)
        # return this device's storage shard
        per_dev = m // s
        return jax.lax.dynamic_slice_in_dim(outputs, idx * per_dev,
                                            per_dev, 0)

    return run(stage_params, x)


def stack_stage_params(params_list) -> Any:
    """[stage0_params, stage1_params, ...] (same structure) → stacked
    pytree with leading S axis, ready for P('model') sharding."""
    return jax.tree.map(lambda *ps: jnp.stack(ps), *params_list)


# -------------------------------------------------- heterogeneous stages

def pack_stages(params_list) -> Tuple[jax.Array, list]:
    """Pack per-stage param pytrees of DIFFERENT structures into one
    (S, L) f32 array (rows zero-padded to the longest stage) plus
    per-stage unpack closures. This is what lets a pipeline span e.g.
    ResNet stages whose block structures differ: the packed rows all
    have the same shape, so they shard over the pipe axis like any
    stacked pytree, and each device reconstitutes its own stage's
    structure locally."""
    import numpy as np

    flats, unpackers = [], []
    for p in params_list:
        leaves, treedef = jax.tree.flatten(p)
        shapes = [l.shape for l in leaves]
        dtypes = [l.dtype for l in leaves]
        for d in dtypes:
            # the packed row is f32; wider/integer leaves would silently
            # lose bits on the round trip
            if not (jnp.issubdtype(d, jnp.floating)
                    and jnp.dtype(d).itemsize <= 4):
                raise TypeError(
                    f"pack_stages supports float leaves of <=32 bits, got "
                    f"{d}; keep non-float state out of the packed stage "
                    f"params")
        sizes = [int(np.prod(s)) if s else 1 for s in shapes]
        offs = np.concatenate([[0], np.cumsum(sizes)]).astype(int)
        flat = (jnp.concatenate([jnp.ravel(l).astype(jnp.float32)
                                 for l in leaves])
                if leaves else jnp.zeros((0,), jnp.float32))
        flats.append(flat)

        def make_unpack(treedef=treedef, shapes=shapes, dtypes=dtypes,
                        offs=offs):
            def unpack(vec: jax.Array):
                ls = [vec[offs[i]:offs[i + 1]].reshape(shapes[i])
                      .astype(dtypes[i]) for i in range(len(shapes))]
                return jax.tree.unflatten(treedef, ls)
            return unpack
        unpackers.append(make_unpack())
    length = max((f.shape[0] for f in flats), default=1)
    packed = jnp.stack([jnp.pad(f, (0, length - f.shape[0]))
                        for f in flats])
    return packed, unpackers


def pipeline_apply_heterogeneous(
    stage_fns,                   # [fn_i(params_i, act) -> act] per stage
    params_list,                 # per-stage pytrees, any structures
    x: jax.Array,                # (M, micro_batch, ...) microbatches
    mesh: Mesh,
    axis_name: str = PIPE_AXIS,
) -> jax.Array:
    """GPipe schedule over stages with different parameter structures.

    Stage params are packed (pack_stages) so every device's shard has
    the same shape; each device dispatches to ITS stage's function via
    ``lax.switch`` on its mesh coordinate (every branch is compiled
    once, the device executes only its own — the SPMD analog of
    per-rank module code in torch pipelines). Activations must still be
    shape-uniform across stage boundaries (the ppermute buffer is
    static); insert adapter layers at stage edges if a model changes
    activation shape.
    """
    s = mesh.shape[axis_name]
    if len(stage_fns) != s or len(params_list) != s:
        raise ValueError(f"need exactly {s} stages for axis "
                         f"{axis_name!r}, got {len(stage_fns)}")
    packed, unpackers = pack_stages(params_list)
    branches = [
        (lambda row, act, f=fn, u=unpack: f(u(row), act))
        for fn, unpack in zip(stage_fns, unpackers)]

    def dispatch(row, act):
        idx = jax.lax.axis_index(axis_name)
        return jax.lax.switch(idx, branches, row, act)

    return _pipeline_schedule(dispatch, packed, x, mesh, axis_name)
