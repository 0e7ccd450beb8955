"""Exp config-as-code system + Swin-MoE model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_tpu.core.experiment import (EXPERIMENTS, BaseExp,
                                              get_exp)
from deeplearning_tpu.core.registry import MODELS


def _tiny_swin_moe():
    return MODELS.build("swin_moe_tiny_patch4_window7_224",
                        num_classes=4, patch_size=2, embed_dim=32,
                        depths=(2, 2), num_heads=(2, 4),
                        num_experts=2, dtype=jnp.float32)


def _moe_loss(model):
    def loss(p, xx):
        logits, aux = model.apply({"params": p}, xx, train=False,
                                  mutable=["losses"])
        ce = -jax.nn.log_softmax(logits)[:, 0].mean()
        return ce + sum(jax.tree.leaves(aux["losses"]))
    return loss


class TestExpSystem:
    def test_registry_and_merge(self):
        exp = get_exp(exp_name="mnist_smoke")
        exp.merge(["base_lr", "0.2", "max_epochs=5"])
        assert exp.base_lr == 0.2 and exp.max_epochs == 5
        with pytest.raises(KeyError):
            exp.merge(["nonexistent", "1"])

    def test_factories_build(self):
        exp = get_exp(exp_name="mnist_smoke")
        model = exp.get_model()
        assert type(model).__name__ == "MnistCNN"
        sched = exp.get_lr_schedule(100)
        assert float(sched(0)) >= 0
        params = model.init(jax.random.key(0),
                            jnp.zeros((1, 28, 28, 1)))["params"]
        tx = exp.get_optimizer(sched, params)
        tx.init(params)

    def test_exp_from_file(self, tmp_path):
        p = tmp_path / "my_exp.py"
        p.write_text(
            "from deeplearning_tpu.core.experiment import BaseExp\n"
            "class Exp(BaseExp):\n"
            "    model_name = 'resnet18'\n"
            "    base_lr = 0.3\n")
        exp = get_exp(exp_file=str(p))
        assert exp.model_name == "resnet18" and exp.base_lr == 0.3


@pytest.fixture(scope="module")
def swin_moe():
    """The tiny Swin-MoE, its weights (``init`` does not depend on the
    batch's size or values) and its jitted loss gradient, built once for
    the tests below."""
    model = _tiny_swin_moe()
    variables = jax.jit(lambda x: model.init(jax.random.key(0), x,
                                             train=False))(
        jnp.zeros((1, 56, 56, 3)))
    return model, variables, jax.jit(jax.grad(_moe_loss(model)))


class TestSwinMoE:
    def test_forward_with_aux_losses(self, swin_moe):
        model, variables, _ = swin_moe
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(2, 56, 56, 3)), jnp.float32)
        out, aux = jax.jit(lambda v, x: model.apply(
            v, x, train=False, mutable=["losses"]))(variables, x)
        assert out.shape == (2, 4)
        auxes = jax.tree.leaves(aux["losses"])
        assert len(auxes) >= 2             # one per MoE block
        assert all(float(a) >= 0 for a in auxes)
        # expert params exist with leading E axis
        flat = jax.tree_util.tree_flatten_with_path(
            variables["params"])[0]
        moe_kernels = [l for kp, l in flat
                       if any("moe_mlp" in str(k) for k in kp)
                       and l.ndim == 3]
        assert moe_kernels and all(k.shape[0] == 2 for k in moe_kernels)

    def test_trainable_with_aux_in_loss(self, swin_moe):
        _, variables, grad = swin_moe
        # the batch of the test below: one compile of the gradient for both
        g = grad(variables["params"], jnp.zeros((4, 56, 56, 3)))
        leaves = [np.asarray(v, np.float64) for v in jax.tree.leaves(g)]
        assert all(np.isfinite(l).all() for l in leaves)
        assert max(np.abs(l).max() for l in leaves) > 0

    def test_expert_parallel_grads_match_unsharded(self, swin_moe):
        """EP training: MOE_RULES shard expert kernels over the expert
        axis; gradients match the unsharded run exactly."""
        from deeplearning_tpu.parallel import MeshConfig, build_mesh
        from deeplearning_tpu.parallel.moe import MOE_RULES
        from deeplearning_tpu.parallel.sharding import (batch_sharding,
                                                        shard_params_tree)
        _, variables, grad = swin_moe
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(4, 56, 56, 3)), jnp.float32)
        params = variables["params"]

        g_ref = grad(params, x)

        mesh = build_mesh(MeshConfig(data=-1, expert=2))
        shardings = shard_params_tree(params, mesh, MOE_RULES)
        ps = jax.device_put(params, shardings)
        # expert kernels really shard over the expert axis
        sharded_leaves = [l for l in jax.tree.leaves(ps)
                          if not l.sharding.is_fully_replicated]
        assert sharded_leaves, "MOE_RULES sharded nothing"
        xs = jax.device_put(x, batch_sharding(mesh))
        g_ep = grad(ps, xs)
        for a, b in zip(jax.tree.leaves(g_ep), jax.tree.leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-3)


class TestMoEObservability:
    """Per-layer routing health metrics (drop rate / capacity utilization /
    load imbalance) surfaced as train-step metrics — the quantities
    swin-moe tunes capacity_factor against
    (swin_transformer_moe.py:273)."""

    def test_moe_metrics_in_train_step(self):
        import optax

        from deeplearning_tpu.core import rng as rng_mod
        from deeplearning_tpu.train import TrainState, make_train_step
        from deeplearning_tpu.train.classification import make_loss_fn

        model = MODELS.build("swin_moe_micro_patch2_window7",
                             num_classes=4, dtype=jnp.float32)
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(8, 28, 28, 3)), jnp.float32)
        y = jnp.asarray(np.random.default_rng(1).integers(0, 4, 8))
        variables = jax.jit(lambda x: model.init(jax.random.key(0), x,
                                                 train=False))(x)
        state = TrainState.create(apply_fn=model.apply,
                                  params=variables["params"],
                                  tx=optax.adam(1e-3))
        step = make_train_step(make_loss_fn())
        state, metrics = step(state, {"image": x, "label": y},
                              rng_mod.root_key(0))
        for key in ("moe/drop_rate", "moe/capacity_util",
                    "moe/max_expert_load"):
            assert key in metrics, sorted(metrics)
        assert 0.0 <= float(metrics["moe/drop_rate"]) <= 1.0
        assert 0.0 < float(metrics["moe/capacity_util"]) <= 1.0
        assert float(metrics["moe/max_expert_load"]) >= 1.0
