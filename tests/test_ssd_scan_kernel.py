"""Mamba-2's chunked scan as Pallas kernels (``ops/pallas/ssd_scan.py``),
interpreted on the CPU, against the lax path it replaces on a TPU
(``ops/ssd.py::ssd_scan``, the oracle) and against the same recurrence in
float32: the output and the gradients of x, Δ, A, B and C, over several
chunks so that the carried state and its gradient cross chunk boundaries;
the path each backend and shape takes; and a Mamba mixer with the kernels
forced against the mixer on the lax path.

What the tolerances allow. Both paths round ``(L ∘ C Bᵀ)`` and ``x Δ`` to
bfloat16 (8 bits) and return y in bfloat16, so the outputs agree but for the
last bit of an element where a product of a few float32 sums rounds the
other way: 2^-7 of the largest output. The lax path's gradient rounds each
cotangent of a bfloat16 value to bfloat16 (``x Δ``'s, twice), the kernels
sum theirs in float32; each rounding is up to 2^-9 of an element, a few of
them add, so the two gradients and each against float32 agree to 1e-2 of
their largest element. On the chip the kernels were compared with the lax
path at the benchmark cell's shape (PERF.md §6, PR 39)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_tpu.ops import ssd
from deeplearning_tpu.ops.pallas import ssd_scan

NAMES = ("x", "dt", "a", "b", "c")


def _inputs(batch, tokens, heads, width, groups, state, seed):
    """bfloat16 x, B and C at the mixer's scale, float32 Δ (log-uniform)
    and A from Mamba-2's ranges."""
    ks = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(ks[0], (batch, tokens, heads, width)
                              ).astype(jnp.bfloat16),
            jnp.exp(jax.random.uniform(ks[1], (batch, tokens, heads),
                                       minval=np.log(1e-3),
                                       maxval=np.log(1e-1))),
            -jax.random.uniform(ks[2], (heads,), minval=1.0, maxval=16.0),
            (0.3 * jax.random.normal(ks[3], (batch, tokens, groups, state))
             ).astype(jnp.bfloat16),
            (0.3 * jax.random.normal(ks[4], (batch, tokens, groups, state))
             ).astype(jnp.bfloat16))


def _within(mine, theirs, tol, name):
    mine, theirs = (np.asarray(t, np.float32) for t in (mine, theirs))
    assert mine.shape == theirs.shape, name
    assert np.all(np.isfinite(mine)), name
    err, top = np.abs(mine - theirs).max(), np.abs(theirs).max()
    assert err <= tol * top, (name, err, top)


# (batch, tokens, heads, head width, groups, state, chunk): three and four
# chunks of 128, heads of 64 two to a 128-lane tile, one and two groups (a
# group's first program makes C Bᵀ, its last writes dB and dC), 16 heads in
# one group: two programs of 512 lanes a chunk, eight heads each; and three
# chunks of the published 256
CASES = {
    "chunk_256": (1, 768, 4, 64, 1, 128, 256),
    "one_group": (1, 384, 4, 64, 1, 128, 128),
    "two_groups": (1, 384, 4, 64, 2, 128, 128),
    "two_rows_two_groups": (2, 512, 4, 64, 2, 128, 128),
    "eight_heads_a_program": (1, 384, 16, 64, 1, 128, 128),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernels_are_the_lax_scan(case):
    batch, tokens, heads, width, groups, state, chunk = CASES[case]
    args = _inputs(batch, tokens, heads, width, groups, state, len(case))
    mix = jax.random.normal(jax.random.key(7), args[0].shape)
    wide = [t.astype(jnp.float32) for t in args]

    def grads(scan, operands):
        return jax.jit(jax.grad(lambda *t: jnp.sum(
            scan(*t, chunk).astype(jnp.float32) * mix), argnums=range(5)))(
                *operands)

    y = jax.jit(ssd_scan.ssd_scan, static_argnums=5)(*args, chunk)
    assert y.dtype == jnp.bfloat16 and y.shape == args[0].shape
    _within(y, jax.jit(ssd.ssd_scan, static_argnums=5)(*args, chunk),
            2 ** -7, "y")
    truth = grads(ssd.ssd_scan, wide)
    for name, mine, lax, exact, arg in zip(
            NAMES, grads(ssd_scan.ssd_scan, args), grads(ssd.ssd_scan, args),
            truth, args):
        assert mine.dtype == arg.dtype, name
        _within(mine, lax, 1e-2, name)
        _within(mine, exact, 1e-2, name)
    # the state crosses chunks: scanned a chunk at a time from zero, the
    # result is another
    alone = jnp.concatenate([ssd_scan.ssd_scan(
        *(t[:, i:i + chunk] if t.ndim > 1 else t for t in args), chunk)
        for i in range(0, tokens, chunk)], axis=1)
    assert float(jnp.abs(alone.astype(jnp.float32) - y).max()) > 0.1 * float(
        jnp.abs(y.astype(jnp.float32)).max())


def test_the_path_by_backend_shape_and_init(monkeypatch):
    """``fused`` on a TPU for bfloat16 x at the cell's shape and at the
    tests'; ``lax`` on the CPU, in ``model.init``, for float32 x, a chunk or a
    state of part of a tile, a row that is no whole number of chunks, heads
    that divide no tile or fill no tile of a group."""
    cell = (8192, 64, 64, 128, 1, 256)
    assert ssd_scan.select_path(*cell, jnp.bfloat16) == "lax"
    monkeypatch.setattr(ssd_scan, "interpret_mode", lambda: False)
    assert ssd_scan.select_path(*cell, jnp.bfloat16) == "fused"
    assert ssd_scan.select_path(384, 4, 64, 128, 2, 128, jnp.bfloat16) \
        == "fused"
    assert ssd_scan.select_path(*cell, jnp.bfloat16, initializing=True) \
        == "lax"
    assert ssd_scan.select_path(*cell, jnp.float32) == "lax"
    for shape in ((8192, 64, 64, 128, 1, 64), (8192, 64, 64, 64, 1, 256),
                  (8000, 64, 64, 128, 1, 256), (8192, 64, 80, 128, 1, 256),
                  (8192, 2, 64, 128, 2, 256), (32, 4, 16, 16, 2, 8)):
        assert ssd_scan.select_path(*shape, jnp.bfloat16) == "lax", shape
    assert ssd_scan.program_lanes(64, 64, 1) == 512
    assert ssd_scan.program_lanes(4, 64, 2) == 128
    assert ssd_scan.program_lanes(6, 64, 1) == 384


def test_mixer_with_the_kernels_is_the_lax_mixer(monkeypatch):
    """A Mamba mixer of 4 heads of 64 and a state of 128 over 384 positions
    in bfloat16, its scan forced onto the kernels (``model.init`` still takes
    the lax path), against the same mixer on the lax path: output and every
    parameter's gradient; the flight ring names the path each took."""
    from deeplearning_tpu.models.language import granite
    from deeplearning_tpu.obs import flight
    cfg = granite.GraniteConfig(hidden_size=128, mamba_n_heads=4,
                                mamba_d_head=64, mamba_d_state=128,
                                mamba_chunk_size=128)
    mixer = granite.MambaMixer(cfg, jnp.bfloat16)
    u = jax.random.normal(jax.random.key(3), (1, 384, 128), jnp.float32)
    mix = jnp.cos(jnp.arange(u.size, dtype=jnp.float32)).reshape(u.shape)

    def run(path):
        monkeypatch.setattr(
            ssd_scan, "select_path", lambda *a, initializing=False:
            "lax" if initializing or path == "lax" else "fused")
        params = mixer.init(jax.random.key(4), u)["params"]
        return jax.value_and_grad(lambda p: jnp.sum(mixer.apply(
            {"params": p}, u).astype(jnp.float32) * mix))(params), \
            mixer.apply({"params": params}, u)

    (_, g_lax), y_lax = run("lax")
    (_, g), y = run("fused")
    paths = {e["path"] for e in flight.get_recorder().events("kernel")
             if e.get("name") == "ssd_scan" and e["shape"][1] == 384}
    assert paths == {"lax", "fused"}
    _within(y, y_lax, 2 ** -7, "y")
    for (key, mine), theirs in zip(jax.tree_util.tree_leaves_with_path(g),
                                   jax.tree.leaves(g_lax)):
        _within(mine, theirs, 1e-2, jax.tree_util.keystr(key))
