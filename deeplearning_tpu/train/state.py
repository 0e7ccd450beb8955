"""TrainState: params + optimizer + step + EMA + batch stats, one pytree.

The reference scatters this state across objects per-project: model,
optimizer, lr_scheduler, GradScaler, epoch, max_accuracy, and a separate
ModelEMA deep-copy (YOLOX yolox/utils/ema.py:22, yolov5
utils/torch_utils.py:308). Here it is one flat pytree so the whole training
state jits, shards, and checkpoints atomically.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.struct
import jax
import jax.numpy as jnp
import optax


class TrainState(flax.struct.PyTreeNode):
    step: jax.Array
    params: Any
    opt_state: optax.OptState
    batch_stats: Any = None          # mutable BN stats ({} for stateless nets)
    ema_params: Any = None           # decayed shadow of params (None = off)
    ema_decay: float = flax.struct.field(pytree_node=False, default=0.9998)
    tx: optax.GradientTransformation = flax.struct.field(pytree_node=False,
                                                         default=None)
    apply_fn: Callable = flax.struct.field(pytree_node=False, default=None)

    @classmethod
    def create(cls, *, apply_fn: Callable, params: Any,
               tx: optax.GradientTransformation,
               batch_stats: Any = None,
               use_ema: bool = False, ema_decay: float = 0.9998) -> "TrainState":
        return cls(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=tx.init(params),
            batch_stats=batch_stats if batch_stats is not None else {},
            ema_params=jax.tree.map(jnp.copy, params) if use_ema else None,
            ema_decay=ema_decay,
            tx=tx,
            apply_fn=apply_fn,
        )

    def apply_gradients(self, grads: Any, new_batch_stats: Any = None
                        ) -> "TrainState":
        # scopes of ``train/steps.py::STEP_SCOPES``
        with jax.named_scope("optimizer"):
            updates, new_opt_state = self.tx.update(grads, self.opt_state,
                                                    self.params)
            new_params = optax.apply_updates(self.params, updates)
        new_ema = self.ema_params
        if new_ema is not None:
            # YOLOX-style warmup-aware decay: d = decay*(1-exp(-step/2000))
            # (yolox/utils/ema.py:40) keeps early EMA close to raw params.
            with jax.named_scope("ema"):
                d = self.ema_decay * (1.0 - jnp.exp(-(self.step + 1) / 2000.0))
                new_ema = jax.tree.map(
                    lambda e, p: e * d + p.astype(e.dtype) * (1 - d),
                    new_ema, new_params)
        return self.replace(
            step=self.step + 1,
            params=new_params,
            opt_state=new_opt_state,
            batch_stats=(new_batch_stats if new_batch_stats is not None
                         else self.batch_stats),
            ema_params=new_ema,
        )

    def shard_summary(self) -> dict:
        """JSON-able layout description (which leaves are sharded, how)
        — embedded in checkpoint topology sidecars so a cross-topology
        resume can report the layout it is resharding FROM."""
        from ..parallel.sharding import shard_layout_summary
        return shard_layout_summary(
            {"params": self.params, "opt_state": self.opt_state})

    @property
    def eval_params(self) -> Any:
        return self.ema_params if self.ema_params is not None else self.params

    def variables(self, params: Optional[Any] = None) -> dict:
        v = {"params": params if params is not None else self.params}
        if self.batch_stats:
            v["batch_stats"] = self.batch_stats
        return v
