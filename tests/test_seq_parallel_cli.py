"""Sequence parallelism as a USER-FACING training option:
train.mesh_seq_axis + train.seq_parallel build the ring/Ulysses attn_fn
into the model through tools/train.py (long-context training is
first-class, not a library-only capability)."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

COMMON = ["model.name=vit_micro_patch4_56", "model.num_classes=4",
          "data.synthetic=true", "data.image_size=32", "data.channels=3",
          "data.n_train=16", "data.global_batch=8", "train.epochs=1"]


@pytest.mark.parametrize("flavor", ["ring", "ulysses"])
def test_sp_training_through_cli(flavor, capsys):
    from train import main
    rc = main(COMMON + ["train.mesh_seq_axis=2",
                        f"train.seq_parallel={flavor}"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "loss_sum" in out


def test_unknown_flavor_rejected():
    from train import main
    with pytest.raises(ValueError, match="seq_parallel"):
        main(COMMON + ["train.mesh_seq_axis=2",
                       "train.seq_parallel=nope"])


def test_mae_with_ring_attn_matches_plain():
    """MAE pretraining composes with SP: same loss with and without the
    ring attn_fn (the ring is exact attention)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning_tpu.core.registry import MODELS
    from deeplearning_tpu.parallel import MeshConfig, build_mesh
    from deeplearning_tpu.parallel.ring_attention import make_ring_attn_fn

    mesh = build_mesh(MeshConfig(data=-1, seq=2))
    imgs = jnp.asarray(np.random.default_rng(0).normal(
        size=(2, 32, 32, 3)), jnp.float32)
    rng = jax.random.key(1)
    plain = MODELS.build("mae_vit_small_patch16", patch_size=8,
                         dtype=jnp.float32)
    variables = plain.init(jax.random.key(0), imgs, train=False, rng=rng)
    ringed = MODELS.build("mae_vit_small_patch16", patch_size=8,
                          dtype=jnp.float32,
                          attn_fn=make_ring_attn_fn(mesh))
    loss_p, _, _ = plain.apply(variables, imgs, train=False, rng=rng)
    loss_r, _, _ = jax.jit(
        lambda v, x: ringed.apply(v, x, train=False, rng=rng))(
        variables, imgs)
    np.testing.assert_allclose(float(loss_r), float(loss_p), rtol=1e-4)


def test_3d_parallel_train_step():
    """DP x TP x SP composed in ONE train step: batch over data, params
    over model (TRANSFORMER_TP_RULES), attention tokens over seq (ring
    adapter). Loss must be finite and match the plain DP run."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning_tpu.core.registry import MODELS
    from deeplearning_tpu.parallel import MeshConfig, build_mesh
    from deeplearning_tpu.parallel.ring_attention import make_ring_attn_fn
    from deeplearning_tpu.parallel.sharding import (TRANSFORMER_TP_RULES,
                                                    batch_sharding)
    from deeplearning_tpu.train import (TrainState, make_train_step,
                                        shard_state)
    from deeplearning_tpu.train.classification import make_loss_fn
    import optax

    mesh = build_mesh(MeshConfig(data=2, model=2, seq=2))
    g = np.random.default_rng(0)
    batch = {"image": jnp.asarray(g.normal(size=(8, 32, 32, 3)),
                                  jnp.float32),
             "label": jnp.asarray(g.integers(0, 4, 8), jnp.int32)}

    def build(attn_fn, msh, rules):
        model = MODELS.build("vit_base_patch16_224", num_classes=4,
                             img_size=32, patch_size=8, embed_dim=32,
                             depth=2, num_heads=4, dtype=jnp.float32,
                             attn_fn=attn_fn)
        params = model.init(jax.random.key(0),
                            jnp.zeros((1, 32, 32, 3)),
                            train=False)["params"]
        state = shard_state(
            TrainState.create(apply_fn=model.apply, params=params,
                              tx=optax.sgd(0.01)), msh, rules)
        step = make_train_step(make_loss_fn(), mesh=msh)
        data = jax.device_put(batch, batch_sharding(msh))
        return step(state, data, jax.random.key(1))

    state3, m3 = build(make_ring_attn_fn(mesh), mesh,
                       TRANSFORMER_TP_RULES)
    mesh_dp = build_mesh(MeshConfig(data=-1))
    state1, m1 = build(None, mesh_dp, None)
    assert np.isfinite(float(m3["loss"]))
    np.testing.assert_allclose(float(m3["loss"]), float(m1["loss"]),
                               rtol=1e-4)
    for a, b in zip(jax.tree.leaves(state3.params),
                    jax.tree.leaves(state1.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-3)
