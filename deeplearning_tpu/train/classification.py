"""Classification task wiring: loss_fn + metric_fn for the shared step.

The per-batch logic of every archetype-A/B project's train_one_epoch /
evaluate pair (classification/mnist/utils.py:30-90, swin main.py:171-278)
expressed as the two pure functions the jitted steps consume. Supports
integer labels, label smoothing, and mixup soft targets (swin
main.py:111-118 criterion selection).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..evaluation.metrics import topk_correct
from ..ops import losses
from .state import TrainState


def make_loss_fn(label_smoothing: float = 0.0, has_batch_stats: bool = False,
                 aux_weight: float = 0.3):
    """``aux_weight`` handles models returning (logits, aux_logits_tuple)
    in train mode (GoogLeNet aux heads — the reference harness weighs the
    aux CE by 0.3)."""
    def loss_fn(params: Any, state: TrainState, batch: Dict, rng: jax.Array
                ) -> Tuple[jax.Array, Dict]:
        variables = state.variables(params)
        kwargs = dict(train=True, rngs={"dropout": rng})
        aux: Dict[str, Any] = {}
        # "losses" collects model-internal auxiliary losses (e.g. MoE
        # load-balance, sown by MoEMlp) — always harvested into the loss
        logits, mutated = state.apply_fn(
            variables, batch["image"],
            mutable=["batch_stats", "losses", "moe_metrics"],
            **kwargs)
        if has_batch_stats:
            aux["batch_stats"] = mutated["batch_stats"]
        with jax.named_scope("loss_head"):     # steps.py::STEP_SCOPES
            model_aux_losses = jax.tree.leaves(mutated.get("losses", {}))
            aux_logits = ()
            if isinstance(logits, tuple):
                logits, aux_logits = logits
            labels = batch["label"]
            if labels.ndim == logits.ndim:          # mixup soft targets
                loss = losses.soft_target_cross_entropy(logits, labels)
                acc_labels = jnp.argmax(labels, -1)
            else:
                loss = losses.cross_entropy(logits, labels, label_smoothing)
                acc_labels = labels
            for a in aux_logits:
                if a is not None and labels.ndim < logits.ndim + 1:
                    loss = loss + aux_weight * losses.cross_entropy(
                        a, acc_labels, label_smoothing)
            for al in model_aux_losses:
                loss = loss + al
            acc = jnp.mean((jnp.argmax(logits, -1) == acc_labels).astype(
                jnp.float32))
        aux["metrics"] = {"accuracy": acc}
        # surface per-layer MoE routing health as step metrics (mean over
        # layers for drop/util, max over layers for load imbalance)
        moe = mutated.get("moe_metrics", {})
        if moe:
            known = ("drop_rate", "capacity_util", "max_expert_load")
            by_name: Dict[str, list] = {}
            for path, leaf in jax.tree_util.tree_leaves_with_path(moe):
                pstr = jax.tree_util.keystr(path)
                name = next((k for k in known if k in pstr), None)
                if name is None:
                    continue
                by_name.setdefault(name, []).append(jnp.mean(leaf))
            for name, vals in by_name.items():
                stacked = jnp.stack(vals)
                aux["metrics"][f"moe/{name}"] = (
                    jnp.max(stacked) if name == "max_expert_load"
                    else jnp.mean(stacked))
        return loss, aux
    return loss_fn


def make_metric_fn(ks=(1, 5)):
    def metric_fn(params: Any, state: TrainState, batch: Dict) -> Dict:
        logits = state.apply_fn(state.variables(params), batch["image"],
                                train=False)
        counts = topk_correct(logits, batch["label"], ks)
        counts["loss_sum"] = losses.cross_entropy(
            logits, batch["label"]) * batch["label"].shape[0]
        return counts
    return metric_fn
