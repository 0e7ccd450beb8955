"""Language-model task wiring: loss_fn + metric_fn for the shared step,
beside ``train/classification.py``.

A batch is ``{"tokens": (B, S + 1) int32}``, every row one document. The
model reads tokens ``0 .. S-1``; the main head predicts token ``i + 1`` and,
where the model has a multi-token-prediction module, that head predicts
token ``i + 2``. Loss = CE(main) + ``mtp_weight`` x CE(mtp), each a mean
over the positions that have a target (the MTP head's last position has
none); a decoder with one head (``mellum``) hands back one hidden state and
its loss is CE(main) alone, with no ``loss_mtp`` among the metrics. The cross entropy is taken over blocks of ``block_rows`` positions so
that the float32 logits of all positions (16,384 x 19,360 x 4 bytes a head
in the benchmark's cell) never stand whole: a block's logits are recomputed
in the backward pass. The head's kernel and the divisor of its logits are
the model's to say (``decoder.CausalLM.head``): a tied head is the embedding,
transposed.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .state import TrainState

_COUNTERS = ("rows_held", "rows_absent", "buffer_rows", "load_max_over_mean")


def blocked_cross_entropy(hidden: jax.Array, kernel: jax.Array,
                          targets: jax.Array, weights: jax.Array,
                          block_rows: int = 2048, logits_scaling: float = 1.0
                          ) -> Tuple[jax.Array, jax.Array]:
    """Sum over positions of ``weights`` x cross entropy of ``hidden @
    kernel / logits_scaling`` against ``targets``, and of ``weights`` x
    (argmax == target). hidden (N, D) in the compute dtype, kernel (D, V);
    the logits are float32 (the product accumulates in float32) and live a
    block at a time."""
    n, d = hidden.shape
    rows = block_rows if n % block_rows == 0 else n
    kernel = kernel.astype(hidden.dtype)

    @jax.checkpoint
    def block(carry, xs):
        h, t, w = xs
        logits = jnp.dot(h, kernel, preferred_element_type=jnp.float32)
        if logits_scaling != 1.0:
            logits = logits / logits_scaling
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        hit = (jnp.argmax(logits, axis=-1) == t).astype(jnp.float32)
        return (carry[0] + jnp.sum((lse - picked) * w),
                carry[1] + jnp.sum(hit * w)), None

    zero = jnp.zeros((), jnp.float32)
    (loss_sum, hits), _ = jax.lax.scan(
        block, (zero, zero),
        (hidden.reshape(n // rows, rows, d), targets.reshape(-1, rows),
         weights.reshape(-1, rows)))
    return loss_sum, hits


def _head(params: Any, state: TrainState) -> Tuple[jax.Array, float]:
    """(the head's kernel (D, V), the divisor of its logits), from the model
    (``decoder.CausalLM.head``): its own ``head`` or, tied, the embedding."""
    return state.apply_fn(state.variables(params), method="head")


def _head_sums(params: Any, state: TrainState, tokens: jax.Array,
               block_rows: int):
    """((loss_sum, hits, positions) per head, the sown ``moe_metrics``) of a
    training pass over ``tokens`` (B, S + 1)."""
    b, s = tokens.shape[0], tokens.shape[1] - 1
    hidden, mutated = state.apply_fn(
        state.variables(params), tokens[:, :-1], train=True,
        next_tokens=tokens[:, 1:], return_hidden=True,
        mutable=["moe_metrics"])
    with jax.named_scope("loss_head"):         # steps.py::STEP_SCOPES
        kernel, scaling = _head(params, state)
    out = []
    for ahead, h in enumerate(hidden, start=1):
        # the head `ahead` tokens on: position i has a target while
        # i + ahead <= S
        with jax.named_scope("loss_head"):     # steps.py::STEP_SCOPES
            targets = jnp.pad(tokens[:, ahead:], ((0, 0), (0, ahead - 1)))
            weights = jnp.broadcast_to(
                (jnp.arange(s) <= s - ahead).astype(jnp.float32), (b, s))
            loss_sum, hits = blocked_cross_entropy(
                h.reshape(b * s, -1), kernel, targets.reshape(-1),
                weights.reshape(-1), block_rows, scaling)
        out.append((loss_sum, hits, float(b * (s - ahead + 1))))
    return out, mutated.get("moe_metrics", {})


def make_loss_fn(mtp_weight: float = 0.3, block_rows: int = 2048):
    def loss_fn(params: Any, state: TrainState, batch: Dict, rng: jax.Array
                ) -> Tuple[jax.Array, Dict]:
        heads, sown = _head_sums(params, state, batch["tokens"], block_rows)
        (main, hits, count), *ahead = heads
        loss = main / count
        metrics = {"accuracy": hits / count, "loss_main": loss}
        for extra, _, n in ahead:
            metrics["loss_mtp"] = extra / n
            loss = loss + mtp_weight * extra / n
        # the expert layers' counters, by layer, as the step's metrics
        for path, leaf in jax.tree_util.tree_leaves_with_path(sown):
            keys = [str(getattr(k, "key", k)) for k in path]
            name = next((k for k in keys if k in _COUNTERS), None)
            if name is not None:
                layer = "/".join(k for k in keys[:keys.index(name)]
                                 if k != "moe")
                metrics[f"moe/{name}/{layer}"] = jnp.mean(
                    leaf.astype(jnp.float32))
        return loss, {"metrics": metrics}
    return loss_fn


def make_metric_fn(block_rows: int = 2048):
    """Per-batch sums for ``make_eval_step``: next-token loss and hits of
    the main head, ``count`` the positions."""
    def metric_fn(params: Any, state: TrainState, batch: Dict) -> Dict:
        # the main head alone reads one token ahead; evaluated without the
        # MTP module's block
        tokens = batch["tokens"]
        b, s = tokens.shape[0], tokens.shape[1] - 1
        hidden = state.apply_fn(state.variables(params), tokens[:, :-1],
                                train=False, return_hidden=True)[0]
        kernel, scaling = _head(params, state)
        loss_sum, hits = blocked_cross_entropy(
            hidden.reshape(b * s, -1), kernel, tokens[:, 1:].reshape(-1),
            jnp.ones((b * s,), jnp.float32), block_rows, scaling)
        return {"loss_sum": loss_sum, "top1": hits,
                "count": jnp.asarray(b * s, jnp.float32)}
    return metric_fn
