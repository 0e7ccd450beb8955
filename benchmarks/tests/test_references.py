"""The plain references against the program's models at small sizes, float32
on both sides, and the stochastic-depth masks recomputed from the seed."""
import jax
import jax.numpy as jnp
import pytest

from benchmarks.references import droppath, swin, train_ref, vit

VIT = dict(patch_size=4, hidden_size=128, num_layers=6, num_heads=4, mlp_ratio=4,
           num_classes=10, image_size=56, drop_path_rate=0.0)
SWIN = dict(patch_size=2, hidden_size=64, depths=[2, 2, 4], num_heads=[2, 4, 8],
            window_size=7, mlp_ratio=4, num_classes=10, image_size=56,
            drop_path_rate=0.3)


@pytest.mark.parametrize("fam,name,shapes,kw,train", [
    (vit, "vit_micro_patch4_56", VIT, {}, False),
    (swin, "swin_mini_patch2_window7", SWIN, {"drop_path_rate": 0.3}, False),
    (swin, "swin_mini_patch2_window7", SWIN, {"drop_path_rate": 0.3}, True),
])
def test_reference_matches_program_model(fam, name, shapes, kw, train):
    import deeplearning_tpu.models  # noqa: F401
    from deeplearning_tpu.core.registry import MODELS
    with jax.default_matmul_precision("highest"):
        params = train_ref.make_params(fam.param_spec(shapes), 7)
        model = MODELS.build(name, num_classes=shapes["num_classes"],
                             dtype=jnp.float32, **kw)
        size = shapes["image_size"]
        x = jax.random.normal(jax.random.key(1), (4, size, size, 3))
        theirs = model.init(jax.random.key(0), x[:1], train=False)["params"]
        assert jax.tree.map(lambda a: a.shape, theirs) == \
            jax.tree.map(lambda a: a.shape, params)
        if train:
            want = model.apply({"params": params}, x, train=True,
                               rngs={"dropout": droppath.step_key(5, 3)})
            keep = droppath.keep_factors(5, 3, fam.droppath_sites(shapes), 4)
            assert float(jnp.min(keep)) == 0.0      # something was dropped
        else:
            want, keep = model.apply({"params": params}, x, train=False), None
        got = fam.forward(params, x, shapes, "f32", keep)
    assert float(jnp.max(jnp.abs(want - got))) < 1e-5


def test_precision_modes_are_ordered():
    """bf16 lies nearer the float32 reference than fp8 does."""
    params = train_ref.make_params(vit.param_spec(VIT), 3)
    x = jax.random.normal(jax.random.key(2), (4, 56, 56, 3))
    ref = vit.forward(params, x, VIT, "f32")
    gap = {m: float(jnp.max(jnp.abs(vit.forward(params, x, VIT, m) - ref)))
           for m in ("bf16", "fp8")}
    assert 0 < gap["bf16"] * 3 < gap["fp8"]
