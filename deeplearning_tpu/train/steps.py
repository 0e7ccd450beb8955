"""Jitted train/eval steps: GSPMD sharding, grad accumulation, remat.

This single function replaces the reference's per-project hot loops
(classification/mnist/utils.py:30 train_one_epoch; swin main.py:171-229
with AMP scaler + accumulation; YOLOX trainer.py:90 train_one_iter):

- data parallelism: the batch is sharded over the mesh's data axes and the
  loss is a mean over the GLOBAL batch, so ``jax.grad`` under GSPMD yields
  exactly DDP's all-reduced mean gradient — the compiler inserts the ICI
  all-reduce that NCCL did (others/train_with_DDP/train.py:195).
- gradient accumulation: a ``lax.scan`` over microbatches inside one jitted
  step (swin main.py:106,192-200 TRAIN.ACCUMULATION_STEPS analog) — no
  optimizer-state churn between micro-steps.
- bf16 autocast is a model-construction property (dtype policy), not a
  context manager; no loss scaling is needed on TPU (core/precision.py).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import rng as rng_mod
from ..obs import flight
from ..parallel import collectives
from ..parallel.mesh import DATA_AXIS, FSDP_AXIS
from ..parallel.sharding import (batch_spec, opt_state_shardings,
                                 shard_params_tree, zero1_partition_spec,
                                 zero1_shardings, Rules)
from .state import TrainState

# loss_fn(params, state, batch, rng, train) -> (loss, aux)
# aux: {'batch_stats': new_stats (optional), 'metrics': {...} (optional)}
LossFn = Callable[[Any, TrainState, Any, jax.Array], Tuple[jax.Array, Dict]]

# The step's own ``jax.named_scope``s: what a device trace calls the work
# that no Flax module names. Each is opened where the work happens (the
# loss_fns, here, ``TrainState.apply_gradients``, the decoders' attention)
# and read by a per-layer metric of the benchmark (PERF.md, section 3); they
# are metadata of the compiled step and cost nothing at run time. The
# kernels' scopes (``mla_core``, ``moe_dispatch``, ...) are their layers'.
STEP_SCOPES = ("loss_head", "grad_cast", "optimizer", "ema", "step_metrics",
               "rotary", "head_split")


def _microbatch(batch: Any, accum_steps: int, i: jax.Array) -> Any:
    def slice_leaf(x):
        micro = x.shape[0] // accum_steps
        return jax.lax.dynamic_slice_in_dim(x, i * micro, micro, axis=0)
    return jax.tree.map(slice_leaf, batch)


def make_train_step(
    loss_fn: LossFn,
    mesh: Optional[Mesh] = None,
    accum_steps: int = 1,
    donate: bool = True,
    donate_batch: bool = False,
    weight_update: str = "replicated",
    grad_comm: str = "fp32",
    rules: Optional[Rules] = None,
    comm_block: int = 256,
) -> Callable[[TrainState, Any, jax.Array], Tuple[TrainState, Dict]]:
    """Build the jitted train step. ``batch`` leaves must have a leading
    global-batch dim divisible by ``accum_steps`` (and by the data-axis
    size when a mesh is given).

    ``donate_batch=True`` additionally donates the batch argument
    (``donate_argnums=(0, 1)``): the input's HBM buffers are recycled by
    XLA instead of a fresh allocation per step — right for pipeline-fed
    batches that are used exactly once (the DevicePrefetcher/Trainer hot
    loop). Keep it off (the default) when the caller reuses a batch
    across calls, e.g. single-batch microbenchmarks.

    ``weight_update="zero1"`` (requires ``mesh``, pair it with
    ``shard_state(..., zero1=True)``) constrains gradients to the
    data-sharded optimizer-moment layout before ``apply_gradients`` and
    the new params back to the param layout after, so XLA lowers the DDP
    gradient all-reduce into reduce-scatter -> per-shard update ->
    all-gather instead of keeping full moments per device. ``rules`` must
    be the same TP/FSDP rules the state was sharded with.

    ``grad_comm="int8"`` (requires ``mesh``, ``accum_steps == 1``, no
    ``rules``, and a loss without batch_stats) computes per-replica local
    gradients under shard_map and reduces them with EQuARX-style
    block-scaled int8 collectives (block size ``comm_block``) instead of
    the implicit fp32 GSPMD all-reduce — combined with zero1, divisible
    leaves ride an int8 reduce-scatter and emerge already moment-sharded."""
    if weight_update not in ("replicated", "zero1"):
        raise ValueError(f"weight_update must be 'replicated' or 'zero1', "
                         f"got {weight_update!r}")
    if grad_comm not in ("fp32", "int8"):
        raise ValueError(f"grad_comm must be 'fp32' or 'int8', "
                         f"got {grad_comm!r}")
    if (weight_update == "zero1" or grad_comm == "int8") and mesh is None:
        raise ValueError("weight_update='zero1' / grad_comm='int8' need a mesh")
    if grad_comm == "int8" and accum_steps != 1:
        raise ValueError("grad_comm='int8' requires accum_steps == 1 "
                         "(the scan path already accumulates in fp32; "
                         "quantizing microbatch partial sums would stack "
                         "quantization error accum_steps times)")
    if grad_comm == "int8" and rules:
        raise ValueError("grad_comm='int8' is data-parallel only: TP/FSDP "
                         "rules shard params, but the shard_map grad path "
                         "replicates them")

    def step_fn(state: TrainState, batch: Any, rng: jax.Array
                ) -> Tuple[TrainState, Dict]:
        rng = rng_mod.step_key(rng, state.step)
        if mesh is not None:
            batch = jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, batch_spec())), batch)

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        if grad_comm == "int8":
            (loss, aux), grads = _int8_value_and_grad(
                loss_fn, state, batch, rng, mesh,
                zero1=(weight_update == "zero1"), block=comm_block)
        elif accum_steps == 1:
            (loss, aux), grads = grad_fn(state.params, state, batch, rng)
            # fp32 gradient policy: the scan path below accumulates in
            # fp32; hand optax the same dtype here so bf16-param runs see
            # identical optimizer numerics at accum_steps 1 and N.
            with jax.named_scope("grad_cast"):
                grads = jax.tree.map(
                    lambda g: g.astype(jnp.float32), grads)
        else:
            # batch_stats thread through the scan carry so every
            # microbatch's forward sees the stats advanced by the previous
            # one (matching torch BN across accum_steps forwards), and
            # metrics are averaged over microbatches instead of reporting
            # only the last one.
            aux_proto = _abstract_aux(loss_fn, state, batch, rng,
                                      accum_steps)
            has_stats = "batch_stats" in aux_proto

            def body(carry, i):
                grads_acc, loss_acc, aux_acc = carry
                mb = _microbatch(batch, accum_steps, i)
                st = (state.replace(batch_stats=aux_acc["batch_stats"])
                      if has_stats else state)
                (l, a), g = grad_fn(state.params, st,
                                    mb, jax.random.fold_in(rng, i))
                grads_acc = jax.tree.map(jnp.add, grads_acc, g)
                new_aux = dict(a)
                if "metrics" in a:
                    new_aux["metrics"] = jax.tree.map(
                        jnp.add, aux_acc.get("metrics", {}), a["metrics"])
                return (grads_acc, loss_acc + l, new_aux), None

            init_aux = dict(aux_proto)   # leaves are already jnp.zeros
            if has_stats:
                init_aux["batch_stats"] = state.batch_stats
            zero_grads = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (grads, loss, aux), _ = jax.lax.scan(
                body, (zero_grads, jnp.zeros((), jnp.float32), init_aux),
                jnp.arange(accum_steps))
            grads = jax.tree.map(lambda g: g / accum_steps, grads)
            loss = loss / accum_steps
            if "metrics" in aux:
                aux["metrics"] = jax.tree.map(
                    lambda m: m / accum_steps, aux["metrics"])

        if weight_update == "zero1":
            # grads pinned to the data-sharded moment layout BEFORE the
            # optimizer: GSPMD satisfies the constraint by reduce-scatter
            # (each replica keeps its 1/n shard of the summed grad), so
            # tx.update and apply_updates below run on shards.
            z_sh = zero1_shardings(state.params, mesh, rules)
            grads = jax.tree.map(jax.lax.with_sharding_constraint,
                                 grads, z_sh)

        new_stats = aux.get("batch_stats")
        state = state.apply_gradients(grads, new_stats)

        if weight_update == "zero1":
            # ...and the updated params pinned BACK to the param layout
            # (all-gather of the per-shard updates), moments pinned to
            # the moment layout so they never round-trip to replicated.
            rep = NamedSharding(mesh, P())
            param_sh = shard_params_tree(state.params, mesh, rules)
            param_treedef = jax.tree.structure(state.params)
            opt_sh = opt_state_shardings(state.opt_state, param_treedef,
                                         z_sh, rep)
            ema = state.ema_params
            if (ema is not None
                    and jax.tree.structure(ema) == param_treedef):
                ema = jax.tree.map(jax.lax.with_sharding_constraint,
                                   ema, param_sh)
            state = state.replace(
                params=jax.tree.map(jax.lax.with_sharding_constraint,
                                    state.params, param_sh),
                opt_state=jax.tree.map(jax.lax.with_sharding_constraint,
                                       state.opt_state, opt_sh),
                ema_params=ema)

        metrics = {"loss": loss, **aux.get("metrics", {})}
        with jax.named_scope("step_metrics"):
            metrics["grad_norm"] = jnp.sqrt(sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree.leaves(grads)))
            # device-side divergence flag: the Trainer's deferred-metrics
            # pipeline reads this from the stale snapshot instead of syncing
            # the in-flight loss, so a non-finite step aborts training
            # within the metrics lag with zero extra D2H round-trips
            metrics["bad_step"] = (~jnp.isfinite(loss)).astype(jnp.int32)
        return state, metrics

    donate_argnums: Tuple[int, ...] = ()
    if donate:
        donate_argnums += (0,)
    if donate_batch:
        donate_argnums += (1,)
    return jax.jit(step_fn, donate_argnums=donate_argnums)


def _int8_value_and_grad(loss_fn, state, batch, rng, mesh, zero1, block):
    """Per-replica local grads + EQuARX int8 reduction under shard_map.

    GSPMD's implicit gradient all-reduce cannot be intercepted, so the
    int8 path drops to shard_map over the data axes: each replica
    differentiates the loss over its LOCAL batch shard, then gradients
    are mean-reduced with block-scaled int8 payloads
    (``collectives.quantized_psum`` / ``quantized_reduce_scatter``).
    Under zero1, leaves whose zero1 spec shards dim 0 take the
    reduce-scatter and emerge already moment-sharded; everything else
    (and all leaves when zero1 is off) takes the full quantized psum and
    emerges replicated. Loss and metrics reduce in fp32 pmean — only
    gradients ride the quantized wire."""
    axes = (DATA_AXIS, FSDP_AXIS)
    n = mesh.shape[DATA_AXIS] * mesh.shape[FSDP_AXIS]
    dp = n if zero1 else 1
    z_specs = jax.tree.map(
        lambda p: zero1_partition_spec(tuple(p.shape), dp), state.params)

    def rs_eligible(leaf_shape, spec):
        return (zero1 and len(spec) > 0 and spec[0] is not None
                and leaf_shape[0] % n == 0)

    def local_grad(params, slim, batch, rng):
        # decorrelate per-replica dropout: without the fold every replica
        # would draw the SAME mask pattern over its local batch shard
        rng = jax.random.fold_in(rng, jax.lax.axis_index(axes))
        (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(
            params, slim, batch, rng)
        if "batch_stats" in aux:
            raise ValueError(
                "grad_comm='int8' does not support batch_stats losses: "
                "BN stats would need their own cross-replica reduction "
                "inside shard_map (use SyncBN-free models or fp32 comm)")
        g = jax.tree.map(lambda x: x.astype(jnp.float32), g)

        def reduce_leaf(x, spec):
            if rs_eligible(x.shape, spec):
                return collectives.quantized_reduce_scatter(
                    x, axes, block=block) / n
            return collectives.quantized_psum(x, axes, block=block) / n
        g = jax.tree.map(reduce_leaf, g, z_specs)
        loss = jax.lax.pmean(loss.astype(jnp.float32), axes)
        metrics = jax.tree.map(
            lambda m: jax.lax.pmean(m.astype(jnp.float32), axes),
            aux.get("metrics", {}))
        return (loss, metrics), g

    g_out_specs = jax.tree.map(
        lambda p, spec: spec if rs_eligible(p.shape, spec) else P(),
        state.params, z_specs)
    # the non-array TrainState fields (apply_fn, tx) are pytree-static;
    # params/opt_state/ema are stripped so shard_map only threads the
    # leaves the loss actually reads (step, batch_stats)
    slim = state.replace(params=None, opt_state=None, ema_params=None)
    mapped = jax.shard_map(
        local_grad, mesh=mesh,
        in_specs=(P(), P(), batch_spec(), P()),
        out_specs=((P(), P()), g_out_specs),
        check_vma=False)
    (loss, metrics), grads = mapped(state.params, slim, batch, rng)
    return (loss, {"metrics": metrics} if metrics else {}), grads


def _abstract_aux(loss_fn, state, batch, rng, accum_steps):
    """Zero-valued aux with the right structure for the scan carry."""
    mb = _microbatch(batch, accum_steps, jnp.zeros((), jnp.int32))
    shapes = jax.eval_shape(lambda p, s, b, r: loss_fn(p, s, b, r)[1],
                            state.params, state, mb, rng)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def make_eval_step(
    metric_fn: Callable[[Any, TrainState, Any], Dict],
    mesh: Optional[Mesh] = None,
    use_ema: bool = True,
) -> Callable[[TrainState, Any], Dict]:
    """metric_fn(params, state, batch) -> dict of per-batch metric SUMS
    (summing, not averaging, lets callers weight by true batch size)."""

    def step_fn(state: TrainState, batch: Any) -> Dict:
        if mesh is not None:
            batch = jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, batch_spec())), batch)
        params = state.eval_params if use_ema else state.params
        return metric_fn(params, state, batch)

    return jax.jit(step_fn)


def shard_state(state: TrainState, mesh: Mesh,
                rules: Optional[Rules] = None,
                zero1: bool = False) -> TrainState:
    """Place a TrainState on the mesh: params (and their optimizer-moment /
    EMA mirrors) by ``rules`` — default fully replicated = pure DP — and
    scalars replicated. Optimizer moments that are param-shaped pytrees
    (optax ScaleByAdam mu/nu etc.) inherit the param shardings so TP/FSDP
    states shard consistently.

    ``zero1=True`` shards those moment leaves over the data axes instead
    (ZeRO-1): each device holds 1/dp of mu/nu while params (and EMA) stay
    in their param layout. Pair with
    ``make_train_step(weight_update="zero1")`` so the step keeps them
    there; leaves with no data-divisible dim stay replicated (visible in
    ``shard_layout_summary`` of the opt_state)."""
    rep = NamedSharding(mesh, P())
    param_sh = shard_params_tree(state.params, mesh, rules)
    moment_sh = (zero1_shardings(state.params, mesh, rules)
                 if zero1 else param_sh)
    param_treedef = jax.tree.structure(state.params)

    def mirror(tree):
        """Param shardings where subtree structure matches params, else
        replicated."""
        if tree is None:
            return None
        if jax.tree.structure(tree) == param_treedef:
            return param_sh
        return jax.tree.map(lambda x: rep, tree)

    def on_fallback(opt, e):
        # an un-flattenable field falls back to replicated — fine,
        # but leave a trace: a silently-replicated optimizer state
        # is exactly the HBM regression DLT104 exists to catch
        flight.record("shard_opt_fallback", field=type(opt).__name__,
                      error=repr(e))

    shardings = state.replace(
        step=rep,
        params=param_sh,
        opt_state=opt_state_shardings(state.opt_state, param_treedef,
                                      moment_sh, rep, on_fallback),
        batch_stats=jax.tree.map(lambda x: rep, state.batch_stats),
        ema_params=mirror(state.ema_params),
    )
    return jax.device_put(state, shardings)
