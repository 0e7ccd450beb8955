"""Mixture-of-Experts MLP with expert parallelism over the mesh.

Surface of classification/swin_transformer/models/swin_transformer_moe.py
(:36 MoEMlp → tutel moe_layer with top-k cosine router, capacity factor
:273, aux load-balance loss; :705 global experts = local × world_size).
TPU-native design: the tutel all-to-all dispatch becomes einsum dispatch/
combine tensors under GSPMD — expert parameters carry a leading E axis
sharded over the ``expert`` mesh axis, tokens are sharded over ``data``,
and XLA inserts the all-to-alls from the shardings. Capacity-limited
top-k routing with dropped-token passthrough, fully static shapes.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..obs import flight
from ..ops.pallas import choice_sum
from .mesh import EXPERT_AXIS
from .sharding import Rules
from jax.sharding import PartitionSpec as P

# sharding rules for MoE params: expert-major leading axis
MOE_RULES: Rules = (
    (r"experts/(fc1|fc2)_kernel$", P(EXPERT_AXIS, None, None)),
    (r"experts/(fc1|fc2)_bias$", P(EXPERT_AXIS, None)),
)


def load_balance_loss(router_probs: jax.Array, expert_mask: jax.Array
                      ) -> jax.Array:
    """Switch-style aux loss: E · dot(mean prob per expert, fraction of
    tokens per expert)."""
    e = router_probs.shape[-1]
    density = jnp.mean(expert_mask, axis=0)          # tokens fraction
    density_proxy = jnp.mean(router_probs, axis=0)   # prob mass
    return e * jnp.sum(density * density_proxy)


class ExpertMlp(nn.Module):
    """E parallel MLPs as batched params (leading E axis → shardable)."""
    num_experts: int
    hidden: int
    out_dim: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):            # x: (E, C, D)
        d = x.shape[-1]
        k1 = self.param("fc1_kernel", nn.initializers.lecun_normal(),
                        (self.num_experts, d, self.hidden), jnp.float32)
        b1 = self.param("fc1_bias", nn.initializers.zeros,
                        (self.num_experts, self.hidden), jnp.float32)
        k2 = self.param("fc2_kernel", nn.initializers.lecun_normal(),
                        (self.num_experts, self.hidden, self.out_dim),
                        jnp.float32)
        b2 = self.param("fc2_bias", nn.initializers.zeros,
                        (self.num_experts, self.out_dim), jnp.float32)
        y = jnp.einsum("ecd,edh->ech", x, k1.astype(x.dtype)) \
            + b1[:, None].astype(x.dtype)
        y = nn.gelu(y, approximate=True)
        y = jnp.einsum("ech,eho->eco", y, k2.astype(x.dtype)) \
            + b2[:, None].astype(x.dtype)
        return y


class MoEMlp(nn.Module):
    """Drop-in MLP replacement with top-k capacity-limited routing.

    Returns (output, aux_loss). Dropped tokens pass through as zeros plus
    the residual connection outside handles them (swin-moe behavior).
    """
    num_experts: int = 8
    top_k: int = 1
    capacity_factor: float = 1.25
    hidden_ratio: float = 4.0
    aux_weight: float = 0.01
    drop: float = 0.0
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True
                 ) -> Tuple[jax.Array, jax.Array]:
        b, n, d = x.shape
        t = b * n
        tokens = x.reshape(t, d)
        e = self.num_experts
        capacity = max(int(t / e * self.capacity_factor * self.top_k), 1)

        logits = nn.Dense(e, dtype=jnp.float32, name="router")(
            tokens.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)

        # Scatter/gather dispatch — O(T·d + E·C·d) memory. The previous
        # dense (T, E, C) combine/dispatch tensors are O(T²·d) because
        # C ∝ T/E: at 56px·batch-64 (T=50k) that is terabytes — the
        # round-4 swin_moe_cls_hard56 rc=-9 OOM. Routing semantics are
        # unchanged: top-k argmax rounds, token-order capacity ranks,
        # later rounds offset by earlier slot usage.
        aux = jnp.zeros((), jnp.float32)
        remaining = probs
        used = jnp.zeros((e,), jnp.float32)   # slots taken in prior rounds
        gate_sum = jnp.zeros((t,), jnp.float32)  # selected in-capacity mass
        rounds = []                           # (choice, pos_idx, gate, keep)
        n_assigned = jnp.zeros((), jnp.float32)
        per_expert = jnp.zeros((e,), jnp.float32)
        for k in range(self.top_k):
            choice = jnp.argmax(remaining, axis=-1)              # (T,)
            gate = jnp.take_along_axis(remaining, choice[:, None],
                                       axis=-1)[:, 0]
            mask = jax.nn.one_hot(choice, e)                     # (T, E)
            if k == 0:
                aux = load_balance_loss(probs, mask)
            # position within expert (capacity rank), in token order,
            # OFFSET by slots consumed in earlier top-k rounds so first-
            # and second-choice tokens never collide on a slot
            pos = jnp.sum((jnp.cumsum(mask, axis=0) - 1.0 + used[None, :])
                          * mask, axis=-1)                       # (T,)
            keep = pos < capacity                                # (T,)
            pos_idx = jnp.clip(pos.astype(jnp.int32), 0, capacity - 1)
            rounds.append((choice, pos_idx, gate, keep))
            gate_sum = gate_sum + gate * keep
            n_assigned = n_assigned + jnp.sum(keep, dtype=jnp.float32)
            per_expert = per_expert + jnp.sum(
                mask * keep[:, None], axis=0, dtype=jnp.float32)
            used = used + jnp.sum(mask, axis=0)
            remaining = remaining * (1.0 - mask)

        # observability: the quantities that actually go wrong in MoE
        # training (swin_transformer_moe.py:273 tunes capacity_factor
        # against exactly these) — sown per layer, harvested by the
        # trainer into step metrics
        self.sow("moe_metrics", "drop_rate",
                 1.0 - n_assigned / (t * self.top_k))
        self.sow("moe_metrics", "capacity_util",
                 n_assigned / (e * capacity))
        self.sow("moe_metrics", "max_expert_load",
                 jnp.max(per_expert) / jnp.maximum(
                     jnp.mean(per_expert), 1.0))

        # build the (E, C) slot→token table by scatter (dropped tokens
        # write to a dummy expert row e), then gather tokens into
        # (E, C, d) expert inputs; empty slots stay zero like the dense
        # dispatch einsum produced
        slot_token = jnp.zeros((e + 1, capacity), jnp.int32)
        slot_filled = jnp.zeros((e + 1, capacity), tokens.dtype)
        for choice, pos_idx, gate, keep in rounds:
            safe_e = jnp.where(keep, choice, e)
            slot_token = slot_token.at[safe_e, pos_idx].set(
                jnp.arange(t, dtype=jnp.int32))
            slot_filled = slot_filled.at[safe_e, pos_idx].set(1.0)
        expert_in = tokens[slot_token[:e]] * slot_filled[:e, :, None]
        expert_out = ExpertMlp(e, int(d * self.hidden_ratio), d,
                               self.dtype, name="experts")(expert_in)

        # combine: each token gathers its slot's expert output, weighted
        # by its gate (normalized over the selected in-capacity mass for
        # top-k > 1, the tutel/swin-moe convention)
        out = jnp.zeros((t, d), expert_out.dtype)
        for choice, pos_idx, gate, keep in rounds:
            w = gate * keep
            if self.top_k > 1:
                w = w / jnp.maximum(gate_sum, 1e-9)
            out = out + expert_out[choice, pos_idx] \
                * w[:, None].astype(expert_out.dtype)
        out = nn.Dropout(self.drop, deterministic=deterministic)(out)
        return out.reshape(b, n, d), self.aux_weight * aux


# --------------------------------------------------------------------------
# One chip's share of a sparse expert layer: dropless, told which experts it
# holds and by which rule its router chooses (DeepSeek-V3 / GLM-4.x
# ``noaux_tc`` sigmoid routing; softmax top-k, Mellum2's).

def _pick(idx: jax.Array, scores: jax.Array) -> jax.Array:
    """``scores[t, idx[t, j]]``, picked by comparison: the same values as
    ``take_along_axis``, whose gather of scalars and scatter-add back cost
    0.74 + 1.22 ms a layer on a v5e against 0.22 + 0.22 (PR 33)."""
    return jnp.sum(jnp.where(
        idx[..., None] == jnp.arange(scores.shape[-1]), scores[:, None], 0.0),
        axis=-1)


def sigmoid_route(scores: jax.Array, bias: jax.Array, top_k: int,
                  scale: float) -> Tuple[jax.Array, jax.Array]:
    """``scores`` (T, E) are the sigmoids. The ``top_k`` experts with the
    largest ``scores + bias`` are chosen; their weights come from the
    scores alone, normalised over the chosen and scaled. (T, k) ids and
    float32 weights."""
    _, idx = jax.lax.top_k(scores + bias, top_k)
    chosen = _pick(idx, scores)
    weights = scale * chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
    return idx, weights


def softmax_route(probs: jax.Array, top_k: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """``probs`` (T, E) are the softmax over all experts. The ``top_k``
    largest are chosen; their weights are their probabilities divided by
    their sum. No bias, no scale. (T, k) ids and float32 weights; the
    gradient reaches the router through the softmax."""
    _, idx = jax.lax.top_k(probs, top_k)
    chosen = _pick(idx, probs)
    return idx, chosen / jnp.sum(chosen, -1, keepdims=True)


# what a rule asks of the layer: the activation that makes its scores of the
# router's logits, and whether it takes a correction bias and a scale
sigmoid_route.scores, sigmoid_route.biased = jax.nn.sigmoid, True
softmax_route.scores, softmax_route.biased = jax.nn.softmax, False


# megablox's row tile of the grouped product: the buffer is whole such tiles
_GMM_ROWS = 512


def gmm_tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """megablox tiles (rows, contraction, columns) of an (m, k) x (k, n)
    grouped product, from its shape (megablox asks this of every product it
    makes: the forward one, and the two of its backward pass with their own
    k and n). On a v5e at both decoders' shapes, forward + both gradients
    (PERF.md §6, PR 34): 2,304 -> 1,792 at (512, 768, 896) 4.11 ms against
    5.75 at the fixed (512, 1024, 1024) with its masked remainders, 896 ->
    2,304 2.04 against 2.98, 1,536 -> 2,048 0.94 against 1.19, and 2,048 ->
    3,072 keeps (512, 1024, 1024)."""
    return _GMM_ROWS, _lane_tile(k), _lane_tile(n)


def _lane_tile(x: int, most: int = 1024) -> int:
    """The largest divisor of ``x`` that is whole 128-lane tiles and at most
    ``most``; ``most`` itself (megablox masks the remainder) where there is
    none."""
    fits = [t for t in range(128, most + 1, 128) if x % t == 0]
    return fits[-1] if fits else most


def grouped_route(rows: int, initializing: bool = False) -> str:
    """Which grouped product a row buffer takes, from what the code can see:
    ``megablox`` (the Pallas kernel that ships with JAX) on a TPU where the
    rows fill whole tiles; ``ragged_dot`` on the CPU, for other row counts,
    while ``model.init`` runs the layer once, eagerly, and as the oracle."""
    fits = rows % _GMM_ROWS == 0 and not initializing
    return "megablox" if fits and jax.default_backend() != "cpu" \
        else "ragged_dot"


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   route: str) -> jax.Array:
    """``lhs[rows of group g] @ rhs[g]`` for rows sorted by group: (M, K) x
    (G, K, N) -> (M, N), device work in proportion to ``sum(group_sizes)``,
    which may be anything up to M (the buffer's rows, not the rows present).
    Rows past that sum hold nothing that may be used (``megablox`` leaves
    them unwritten): the caller masks them."""
    with jax.named_scope("expert_matmul"):
        if route == "megablox":
            from jax.experimental.pallas.ops.tpu.megablox import ops as mblx
            return mblx.gmm(lhs, rhs, group_sizes, lhs.dtype, gmm_tiling)
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)


def buffer_capacity(choices: int, held: int, num_experts: int) -> int:
    """Rows of the compact buffer of a layer that holds ``held`` of
    ``num_experts`` experts and routes ``choices`` (tokens x top_k) rows over
    all of them: twice the rows it can expect, rounded up to the grouped
    product's row tile, and never more than ``choices``. The size of a pass,
    not a capacity: a batch that sends more goes through it more than once."""
    tile = _GMM_ROWS
    twice = -(-2 * choices * held // num_experts)
    return min(choices, -(-twice // tile) * tile)


def _swiglu(both: jax.Array) -> jax.Array:
    f = both.shape[-1] // 2
    with jax.named_scope("expert_act"):
        return nn.silu(both[:, :f]) * both[:, f:]


def _sum_choices(src, slot, here, w):
    """Token side of the row buffer: ``sum_j w[t, j] * src[slot[t, j]]`` over
    the choices that are ``here``, in float32: (C, D) -> (T, D). A token has
    ``top_k`` choices wherever they lie, so this reads ``top_k`` x T rows;
    the absent ones all read one row and are masked. The CPU's path and the
    oracle of ``ops/pallas/choice_sum.py``, which reads the present ones
    alone."""
    out = 0.0
    for j in range(slot.shape[1]):
        row = jnp.where(here[:, j, None], src[slot[:, j]], 0)
        out = out + row.astype(jnp.float32) * w[:, j, None]
    return out


def _token_sum(path, src, slot, here, w):
    """``_sum_choices`` by ``path``: ``fused``, the Pallas kernel that reads
    the rows of the choices that are here and no other
    (``ops/pallas/choice_sum.py``, its ``select_path``), or ``lax``."""
    if path == "fused":
        return choice_sum.choice_sum(src, slot, here, w)
    return _sum_choices(src, slot, here, w)


def _pass_index(cap, k, lo, order, inverse, sizes):
    """The pass over sorted rows ``lo .. lo + cap - 1``: how many of each
    expert's rows lie in it, which of its rows are present, the choice each
    is (``top_k`` x token + j) and, for every choice, its row in the buffer
    (clamped) and whether it lies in this pass."""
    n, ends = jnp.sum(sizes), jnp.cumsum(sizes)
    in_pass = jnp.clip(ends, lo, lo + cap) - jnp.clip(ends - sizes, lo, lo + cap)
    choice = jax.lax.dynamic_slice(
        jnp.pad(order, (0, -order.shape[0] % cap)), (lo,), (cap,))
    at = inverse.reshape(-1, k) - lo
    return (in_pass, (lo + jnp.arange(cap))[:, None] < n, choice,
            jnp.clip(at, 0, cap - 1), (at >= 0) & (at < jnp.minimum(cap, n - lo)))


# jitted so that a step's expert layers, and both branches of each, trace and
# lower a pass once (un-jitted, lowering the cell's step took 44 % longer)
@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _pass_fwd(route, sums, cap, lo, tokens, w, gate_up, down, order, inverse,
              sizes):
    """What sorted rows ``lo .. lo + cap - 1`` add to the routed experts'
    output, (T, D) in float32, and what ``_pass_bwd`` reads beside. Every
    array on the rows side has ``cap`` rows."""
    with jax.named_scope("moe_dispatch"):
        in_pass, present, choice, slot, here = _pass_index(
            cap, w.shape[1], lo, order, inverse, sizes)
        rows = jnp.where(present, tokens[choice // w.shape[1]], 0)
    both = grouped_matmul(rows, gate_up, in_pass, route)
    act = _swiglu(both)
    out_rows = grouped_matmul(act, down, in_pass, route)
    with jax.named_scope("moe_combine"):
        out_rows = jnp.where(present, out_rows, 0)
        routed = _token_sum(sums, out_rows, slot, here, w)
    return routed, (rows, both, act, out_rows)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _pass_bwd(route, sums, cap, lo, args, saved, g):
    """Cotangents of ``_pass_fwd``'s tokens, weights and expert kernels.
    Rows-side directions are ``cap``-row gathers from (T, D) arrays, the
    weights' cotangent a dot product a row sent back as a scalar; the
    (T, k, D) cotangent of the weighted sum never stands."""
    tokens, w, gate_up, down, order, inverse, sizes = args
    rows, both, act, out_rows = saved
    with jax.named_scope("moe_combine"):
        in_pass, present, choice, slot, here = _pass_index(
            cap, w.shape[1], lo, order, inverse, sizes)
        g_rows = g[choice // w.shape[1]].astype(jnp.float32)
        d_out = jnp.where(present, (g_rows * w.reshape(-1)[choice][:, None]
                                    ).astype(out_rows.dtype), 0)
        d_w = jnp.where(here, jnp.sum(out_rows.astype(jnp.float32) * g_rows,
                                      -1)[slot], 0)
    product = lambda a, b: grouped_matmul(a, b, in_pass, route)
    # the scopes again, plain: inside ``jax.vjp`` they read
    # ``transpose(jvp(expert_matmul))``, which no reader of op paths matches
    with jax.named_scope("expert_matmul"):
        d_act, d_down = jax.vjp(product, act, down)[1](d_out)
    with jax.named_scope("expert_act"):
        d_both, = jax.vjp(_swiglu, both)[1](d_act)
    with jax.named_scope("expert_matmul"):
        d_rows, d_gate_up = jax.vjp(product, rows, gate_up)[1](d_both)
    with jax.named_scope("moe_dispatch"):
        d_rows = jnp.where(present, d_rows, 0)
        d_tokens = _token_sum(sums, d_rows, slot, here,
                              here.astype(jnp.float32))
    return d_tokens, d_w, d_gate_up, d_down


def _passes(cap, sizes):
    """Passes the rows present take through a buffer of ``cap`` rows."""
    return -(-jnp.sum(sizes) // cap)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _routed(route, sums, cap, tokens, w, gate_up, down, order, inverse, sizes):
    """The routed experts' part: (T, D) tokens, (T, k) float32 weights that
    are 0 where the choice is absent, rows sorted by expert through ``order``
    and ``inverse`` -> (T, D). The rows present go through a buffer of ``cap``
    rows: in one pass where they fit it (what its backward reads is kept),
    in as many as it takes where they do not (summed in float32; the backward
    makes each pass's forward again). Chosen on the device from the row
    count of the batch at hand; every row is computed either way."""
    return _routed_fwd(route, sums, cap, tokens, w, gate_up, down, order,
                       inverse, sizes)[0]


def _one_pass_or_more(cap, order, sizes, one_pass, passes, *operands):
    """``one_pass`` where the buffer holds every choice, else the device's
    pick of it or ``passes`` from the rows present."""
    if cap == order.shape[0]:
        return one_pass(*operands)
    return jax.lax.cond(_passes(cap, sizes) <= 1, one_pass, passes, *operands)


def _routed_fwd(route, sums, cap, *args):
    tokens, _, _, _, order, _, sizes = args

    def one_pass(*args):
        routed, saved = _pass_fwd(route, sums, cap, jnp.int32(0), *args)
        return routed.astype(tokens.dtype), saved

    def passes(*args):
        routed = jax.lax.fori_loop(
            0, _passes(cap, sizes),
            lambda i, sum_: sum_ + _pass_fwd(route, sums, cap, i * cap,
                                             *args)[0],
            jnp.zeros(tokens.shape, jnp.float32))
        return routed.astype(tokens.dtype), jax.tree.map(
            lambda a: jnp.zeros(a.shape, a.dtype),
            jax.eval_shape(one_pass, *args)[1])

    routed, saved = _one_pass_or_more(cap, order, sizes, one_pass, passes,
                                      *args)
    return routed, (args, saved)


def _routed_bwd(route, sums, cap, res, g):
    args, saved = res
    *primal, order, _, sizes = args
    like_primal = lambda grads: tuple(
        d.astype(a.dtype) for d, a in zip(grads, primal))

    def one_pass(args, saved, g):
        return like_primal(_pass_bwd(route, sums, cap, jnp.int32(0), args,
                                     saved, g))

    def passes(args, saved, g):
        def add(i, totals):
            saved = _pass_fwd(route, sums, cap, i * cap, *args)[1]
            grads = _pass_bwd(route, sums, cap, i * cap, args, saved, g)
            return tuple(a + d.astype(a.dtype) for a, d in zip(totals, grads))
        # the tokens' cotangent is summed over the passes in float32 like
        # the output; the kernels' in their own dtype, as steps of gradient
        # accumulation are (float32 sums of them put 0.7 GB on the step's
        # temporaries, compiled for a v5e at 16,384 tokens: PR 33)
        tokens, w, gate_up, down = primal
        return like_primal(jax.lax.fori_loop(
            0, _passes(cap, sizes), add,
            (jnp.zeros(tokens.shape, jnp.float32), jnp.zeros_like(w),
             jnp.zeros_like(gate_up), jnp.zeros_like(down))))

    grads = _one_pass_or_more(cap, order, sizes, one_pass, passes, args,
                              saved, g)
    return (*grads, None, None, None)


_routed.defvjp(_routed_fwd, _routed_bwd)


class HeldExpertsMlp(nn.Module):
    """This chip's share of a sparse expert layer. It routes every token over
    all ``num_experts`` (the published count) by the rule ``route``
    (``sigmoid_route``, ``softmax_route``), computes experts ``first .. first
    + held - 1`` for the rows whose chosen expert it holds, and adds the
    shared experts where the layer has any; what the absent experts would
    have added is left out (the chips that hold them add it in a deployment,
    through an exchange that one chip has not).

    Dropless: no capacity, every chosen held expert is computed under any
    routing. Rows are sorted by expert and the grouped products work on the
    rows present alone. The row buffer, and every gather, mask and activation
    on it, has ``buffer_capacity`` rows: twice this chip's expected share of
    the ``top_k`` x tokens choices. A batch whose rows present fit it goes
    through in one pass; one that sends more (up to every choice of every
    token) goes through the same buffer as often as it takes, which the
    layer decides on the device from the count it has just computed
    (``buffer_rows`` in ``moe_metrics``: the buffer's rows times the passes).
    A layer that holds every expert has a buffer of every choice and no such
    decision.
    SwiGLU experts of width ``hidden``, no biases. ``correction_bias`` is the
    ``noaux_tc`` buffer of the sigmoid rule (a layer routed by softmax has no
    such parameter): it enters the choice, not the weights, and no gradient
    reaches it."""
    num_experts: int = 64
    held: int = 8
    first: int = 0
    top_k: int = 4
    hidden: int = 1536
    shared_experts: int = 1
    routed_scale: float = 1.8
    route: Callable = sigmoid_route
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, n, d = x.shape
        t, k, e, held = b * n, self.top_k, self.num_experts, self.held
        if not 0 <= self.first <= e - held:
            raise ValueError(f"experts {self.first}..{self.first + held - 1} "
                             f"are not among {e}")
        tokens = x.reshape(t, d)
        init = nn.initializers.normal(0.02)
        w_r = self.param("router_kernel", init, (d, e), jnp.float32)
        rule = (k,)
        if self.route.biased:
            bias = self.param("correction_bias", nn.initializers.zeros, (e,),
                              jnp.float32)
            rule = (jax.lax.stop_gradient(bias), k, self.routed_scale)
        cap = buffer_capacity(t * k, held, e)
        with jax.named_scope("moe_dispatch"):
            # the choice is made in float32 as the published code makes it
            # (a float32 product on the MXU needs ``highest`` to be one)
            scores = self.route.scores(jnp.dot(
                tokens.astype(jnp.float32), w_r,
                precision=jax.lax.Precision.HIGHEST))
            idx, weights = self.route(scores, *rule)
            local = idx.reshape(-1) - self.first
            here = (local >= 0) & (local < held)
            key = jnp.where(here, local, held)        # absent rows sort last
            order = jnp.argsort(key, stable=True)
            inverse = jnp.argsort(order)      # a sort again beats a scatter
            sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                            dtype=jnp.int32)
            w = jnp.where(here.reshape(t, k), weights, 0.0)
        rows_held = jnp.sum(sizes)
        self.sow("moe_metrics", "rows_held", rows_held)
        self.sow("moe_metrics", "rows_absent", t * k - rows_held)
        self.sow("moe_metrics", "buffer_rows",
                 cap * jnp.maximum(_passes(cap, sizes), 1))
        self.sow("moe_metrics", "load_max_over_mean",
                 jnp.max(sizes) / jnp.maximum(jnp.mean(
                     sizes.astype(jnp.float32)), 1.0))
        self.sow("intermediates", "choice", idx)

        f = self.hidden
        gate = self.param("experts_gate", init, (held, d, f), jnp.float32)
        up = self.param("experts_up", init, (held, d, f), jnp.float32)
        down = self.param("experts_down", init, (held, f, d), jnp.float32)
        route = grouped_route(cap, self.is_initializing())
        sums = choice_sum.select_path(t, k, d, self.dtype,
                                      initializing=self.is_initializing())
        member = "/".join(self.path)
        flight.tally("kernel", ("expert_matmul", route, cap, d, f, held),
                     member=member, name="expert_matmul",
                     path=route, shape=[cap, d, f, held])
        flight.tally("kernel", ("choice_sum", sums, t, k, d, cap),
                     member=member, name="choice_sum", path=sums,
                     shape=[t, k, d, cap])
        routed = _routed(
            route, sums, cap, tokens.astype(self.dtype), w,
            jnp.concatenate([gate, up], -1).astype(self.dtype),
            down.astype(self.dtype), order, inverse, sizes)
        y = routed.reshape(b, n, d)
        if self.shared_experts:
            y = y + SwiGLU(self.hidden * self.shared_experts, self.dtype,
                           name="shared")(x)
        return y


class SwiGLU(nn.Module):
    """``(silu(x W_g) * (x W_u)) W_d``, no biases."""
    hidden: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        dense = functools.partial(
            nn.Dense, use_bias=False, dtype=self.dtype,
            kernel_init=nn.initializers.normal(0.02))
        h = nn.silu(dense(self.hidden, name="gate")(x)) \
            * dense(self.hidden, name="up")(x)
        return dense(x.shape[-1], name="down")(h)
