"""The token side of the expert layer's row buffer as a Pallas kernel
(``ops/pallas/choice_sum.py``), interpreted on the CPU, against the lax path
it replaces on a TPU (``parallel/moe.py::_sum_choices``, the oracle): bit for
bit at small shapes, whatever share of the choices is here, in a second pass
over the buffer and in both of the layer's uses; and the layer itself with the
fused path forced against the lax path.

Bit for bit needs products that are exact: XLA's CPU backend fuses some of the
lax path's multiplies and adds into FMAs and the interpreted kernel others, so
with weights of full float32 precision the two differ in the last place. The
weights here are bfloat16 values (a bfloat16 row times one is exact in
float32), and ``here`` as weights, the backward pass's, are 0 and 1; the layer
is compared to a few ulps. On the chip the kernel was compared with the lax
path at both cells' shapes with the cells' own weights (PERF.md §6, PR 37)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_tpu.ops.pallas import choice_sum
from deeplearning_tpu.parallel import moe

D = 256


def _routing(tokens, k, experts, held, cap, lo=0, seed=0):
    """The slots and presence of a pass over sorted rows ``lo .. lo + cap -
    1``, as the layer makes them: ``k`` distinct experts of ``experts`` a
    token, those under ``held`` here; and a (cap, D) bfloat16 buffer whose
    rows past the ones in the pass are NaN (no slot of a present choice
    names one, so a read of them shows)."""
    rng = np.random.default_rng(seed)
    idx = np.argsort(rng.random((tokens, experts)), axis=1)[:, :k]
    local = jnp.asarray(idx.reshape(-1))
    key = jnp.where(local < held, local, held)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)
    in_pass, present, _, slot, here = moe._pass_index(
        cap, k, jnp.int32(lo), order, jnp.argsort(order), sizes)
    src = jax.random.normal(jax.random.key(seed + 1), (cap, D))
    src = jnp.where(present, src, jnp.nan).astype(jnp.bfloat16)
    return src, slot, here, int(jnp.sum(in_pass))


# (tokens, top_k, experts, held, buffer rows, first sorted row of the pass,
# token block): 512-row tiles of buffer; a share of 1/8 and of 1/4 (in blocks
# of 256 tokens: the next block's first copies start in the block before),
# none, every choice, and the second pass of a batch that does not fit its
# buffer
CASES = {
    "eighth_k8": (512, 8, 64, 8, 1024, 0, 512),
    "quarter_k4_blocks": (1024, 4, 16, 4, 1024, 0, 256),
    "none_k4": (256, 4, 16, 0, 512, 0, 256),
    "every_k8": (256, 8, 8, 8, 2048, 0, 256),
    "second_pass_k4": (512, 4, 8, 8, 512, 512, 512),
}


@pytest.mark.parametrize("use", ["weights", "here"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_is_the_lax_sum_bit_for_bit(case, use, monkeypatch):
    tokens, k, experts, held, cap, lo, block = CASES[case]
    # a VMEM budget that holds ``block`` tokens and no more
    monkeypatch.setattr(choice_sum, "_VMEM_BUDGET", block * (12 * D + 2048))
    assert choice_sum.token_block(tokens, k, D) == block
    src, slot, here, rows = _routing(tokens, k, experts, held, cap, lo)
    assert int(jnp.sum(here)) == (rows if lo == 0 else min(rows, cap))
    if use == "weights":
        w = jax.random.uniform(jax.random.key(5), here.shape)
        w = jnp.where(here, w, 0.0).astype(jnp.bfloat16).astype(jnp.float32)
    else:
        w = here.astype(jnp.float32)
    mine = np.asarray(choice_sum.choice_sum(src, slot, here, w))
    theirs = np.asarray(jax.jit(moe._sum_choices)(src, slot, here, w))
    assert mine.shape == (tokens, D) and mine.dtype == np.float32
    assert np.all(np.isfinite(mine))
    np.testing.assert_array_equal(mine, theirs)


def test_the_path_by_backend_shape_and_init(monkeypatch):
    """``fused`` on a TPU for bfloat16 rows of whole 256-lane pairs and
    tokens that fill whole blocks; ``lax`` on the CPU, in ``model.init``, for
    other widths, dtypes and token counts; the block fits the budget."""
    assert choice_sum.select_path(16384, 8, 2304, jnp.bfloat16) == "lax"
    monkeypatch.setattr(choice_sum, "interpret_mode", lambda: False)
    assert choice_sum.select_path(16384, 8, 2304, jnp.bfloat16) == "fused"
    assert choice_sum.select_path(16384, 4, 2048, jnp.bfloat16) == "fused"
    assert choice_sum.select_path(16384, 8, 2304, jnp.bfloat16,
                                  initializing=True) == "lax"
    assert choice_sum.select_path(16384, 8, 2304, jnp.float32) == "lax"
    assert choice_sum.select_path(16384, 8, 2240, jnp.bfloat16) == "lax"
    assert choice_sum.select_path(100, 8, 2304, jnp.bfloat16) == "lax"
    assert choice_sum.token_block(16384, 8, 2304) == 1024
    assert choice_sum.token_block(256, 4, 256) == 256


def _layer(monkeypatch, path, capacity=None):
    """A bfloat16 share of 4 of 16 experts at width 256 over 512 tokens, its
    token side on ``path`` (``fused`` runs interpreted here); with
    ``capacity``, a buffer that small."""
    monkeypatch.setattr(
        choice_sum, "select_path", lambda *a, initializing=False, **kw:
        "lax" if initializing or path == "lax" else "fused")
    if capacity:
        monkeypatch.setattr(moe, "buffer_capacity", lambda *a: capacity)
    layer = moe.HeldExpertsMlp(num_experts=16, held=4, top_k=4, hidden=64,
                               dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.key(3), (2, 256, D), jnp.float32)
    p = layer.init(jax.random.key(4), x)["params"]
    mix = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)

    def loss(p, x):
        y, sown = layer.apply({"params": p}, x, mutable=["moe_metrics"])
        return jnp.sum(y.astype(jnp.float32) * mix), (y, sown)
    return jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(p, x)


@pytest.mark.parametrize("capacity", [None, 256], ids=["one_pass", "passes"])
def test_layer_with_the_kernel_is_the_lax_layer(monkeypatch, capacity):
    """Output and every gradient of ``HeldExpertsMlp`` with the token side
    forced onto the kernel equal the lax path's, in one pass and in several
    (a buffer of 256 rows for some 512 of 2,048 choices: the rows that do not
    fit go through it again); the flight ring names the path it took."""
    from deeplearning_tpu.obs import flight
    (_, (y_lax, sown)), g_lax = _layer(monkeypatch, "lax", capacity)
    (_, (y, _)), g = _layer(monkeypatch, "fused", capacity)
    if capacity:
        assert int(sown["moe_metrics"]["buffer_rows"][0]) > capacity
    shapes = [e["shape"] for e in flight.get_recorder().events("kernel")
              if e.get("name") == "choice_sum" and e["path"] == "fused"]
    assert [512, 4, D, capacity or 1024] in shapes
    y_lax, y = (np.asarray(a, np.float32) for a in (y_lax, y))
    np.testing.assert_allclose(y, y_lax, rtol=0, atol=1e-2 * np.abs(
        y_lax).max())
    assert np.mean(y != y_lax) < 0.01      # bfloat16 outputs: a rounding
    for mine, theirs in zip(jax.tree.leaves(g), jax.tree.leaves(g_lax)):
        mine, theirs = np.asarray(mine), np.asarray(theirs)
        np.testing.assert_allclose(mine, theirs, rtol=0,
                                   atol=1e-5 * np.abs(theirs).max() + 1e-30)
