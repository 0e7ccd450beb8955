"""Window attention: fused Pallas kernels vs lax reference + Swin model.

The TPU analog of the reference's only real unit test
(classification/swin_transformer/kernels/window_process/unit_test.py):
fused-kernel forward/backward compared against the unfused reference. On
the CPU backend the kernels run in interpret mode (``common.interpret_mode``)
and the model takes the lax path; the tests that drive the model through the
fused path patch the one selector, ``window_attention.select_path``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_tpu.models.classification import swin
from deeplearning_tpu.obs import flight
from deeplearning_tpu.ops import window_utils as wu
from deeplearning_tpu.ops.pallas import window_attention as pwa


class TestWindowUtils:
    def test_partition_merge_roundtrip(self):
        x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 14, 14, 8)),
                        jnp.float32)
        wins = wu.window_partition(x, 7)
        assert wins.shape == (2 * 4, 49, 8)
        back = wu.window_merge(wins, 7, 14, 14)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(x))

    def test_shift_mask_blocks_cross_region_attention(self):
        mask = wu.shift_window_mask(14, 14, 7, 3)
        assert mask.shape == (4, 49, 49)
        assert (mask == 0).any() and (mask < -1e8).any()
        # window 0 (interior) has no masking
        np.testing.assert_array_equal(mask[0], np.zeros((49, 49)))

    def test_relative_position_index_range(self):
        idx = wu.relative_position_index(7)
        assert idx.shape == (49, 49)
        assert idx.min() >= 0 and idx.max() < 13 * 13
        # symmetric pairs map to mirrored indices; diagonal is the center
        assert len(np.unique(np.diag(idx))) == 1


def _inputs(bw, heads, d, res=14, window=7, masked=True, seed=0):
    """qkv rows, bias and shift mask of ``bw`` windows of a ``res`` grid."""
    rng = np.random.default_rng(seed)
    n = window * window
    qkv = jnp.asarray(rng.normal(0, 0.5, (bw, n, 3 * heads * d)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 0.5, (heads, n, n)), jnp.float32)
    mask = jnp.asarray(wu.shift_window_mask(res, res, window, window // 2)) \
        if masked else None
    return qkv, bias, mask


def _reference(qkv, bias, mask, heads):
    bw, n, c3 = qkv.shape
    return wu.windowed_attention_reference(
        qkv.reshape(bw, n, 3, heads, c3 // 3 // heads), bias, mask)


# (bw, heads, d, res, window, masked): the grid each makes is in the id. The
# kernels' blocks are (N, wb windows, lanes) of the token-major rows, wb a
# multiple of 8 or all of BW
KERNEL_CASES = {
    "shifted_d32_one_program": (8, 3, 32, 14, 7, True),
    "unshifted_d32_shared_bias": (8, 3, 32, 14, 7, False),
    "shifted_d16_images_per_program": (12, 2, 16, 14, 7, True),
    "unshifted_d16": (6, 2, 16, 14, 7, False),
    # nW 4 < 16 windows a program (four images), 20 windows: the last block
    # is ragged and BW is no multiple of 8
    "shifted_bw_not_multiple_of_block": (20, 3, 32, 10, 5, True),
    # the same with 24 windows: a multiple of 8, not of 16
    "shifted_bw_multiple_of_8_not_16": (24, 2, 16, 14, 7, True),
    # no mask, 40 windows of 16 a program: ragged, five heads of 32 (the
    # odd one stands alone)
    "unshifted_ragged_odd_heads_d32": (40, 5, 32, 7, 7, False),
    # nW 64 > windows a program (16): four mask-row blocks an image
    "shifted_nw_larger_than_block": (128, 3, 16, 56, 7, True),
    # nW 9 and 7 heads (an odd one stands alone): no multiple of 8 up to 16
    # pairs with 9, and 27 windows are fewer than 8 images: one program
    "shifted_nw_not_a_power_of_two": (27, 7, 16, 21, 7, True),
    # nW 9, 90 windows: 8 whole images (72 windows) a program, the second
    # block ragged
    "shifted_nw_odd_eight_images_per_program": (90, 3, 16, 21, 7, True),
}


class TestPallasWindowAttention:
    @pytest.mark.parametrize("case", sorted(KERNEL_CASES))
    def test_fused_matches_reference(self, case):
        bw, heads, d, res, window, masked = KERNEL_CASES[case]
        qkv, bias, mask = _inputs(bw, heads, d, res, window, masked)
        out = pwa.window_attention(qkv, bias, mask, heads=heads)
        ref = _reference(qkv, bias, mask, heads)
        assert out.shape == ref.shape == (bw, window * window, heads * d)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("case", sorted(KERNEL_CASES))
    def test_fused_backward_matches_reference(self, case):
        """dqkv and the bias gradient of the fused backward kernel against
        autodiff through the lax path."""
        bw, heads, d, res, window, masked = KERNEL_CASES[case]
        qkv, bias, mask = _inputs(bw, heads, d, res, window, masked)
        weight = jnp.asarray(np.random.default_rng(1).normal(
            size=(bw, window * window, heads * d)), jnp.float32)

        def loss(attend):
            return lambda a, b: jnp.sum(attend(a, b, mask, heads) * weight)

        fused = jax.grad(loss(lambda a, b, m, h: pwa.window_attention(
            a, b, m, heads=h)), argnums=(0, 1))(qkv, bias)
        ref = jax.grad(loss(_reference), argnums=(0, 1))(qkv, bias)
        for name, a, b in zip(("dqkv", "dbias"), fused, ref):
            assert a.shape == b.shape, name
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=5e-5, err_msg=name)

    def test_mask_gets_a_zero_cotangent(self):
        qkv, bias, mask = _inputs(8, 3, 32)
        g = jax.grad(lambda m: jnp.sum(
            pwa.window_attention(qkv, bias, m, heads=3)))(mask)
        np.testing.assert_array_equal(np.asarray(g), np.zeros(mask.shape))

    @pytest.mark.parametrize("bw,nw,heads,want", [
        (8192, 64, 3, 16), (8192, 1, 3, 16), (2048, 16, 6, 16),
        (512, 4, 12, 16), (128, 1, 24, 8), (18, 9, 8, 18), (2, 1, 2, 2),
        (12, 4, 2, 12), (7, 7, 48, 7), (90, 9, 3, 72), (20, 4, 3, 16),
        (512, 4, 24, 8), (128, 4, 16, 8)])
    def test_windows_per_program_from_shapes(self, bw, nw, heads, want):
        wb = pwa.windows_per_program(bw, nw, heads)
        assert wb == want
        assert nw % wb == 0 or wb % nw == 0
        # the blocks' second-minor axis: whole sublane tiles, or all of it
        assert wb % 8 == 0 or wb == bw


def _micro():
    from deeplearning_tpu.core.registry import MODELS
    return MODELS.build("swin_micro_patch2_window7", num_classes=10,
                        dtype=jnp.float32)


def _walk(jaxpr):
    """Every equation outside a ``pallas_call``, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def _largest_square_output(jaxpr, n):
    """Elements of the largest equation output whose trailing shape is
    ``n x n`` or its padded ``slot x slot``."""
    squares = {n, pwa._slot(n)}
    return max((int(np.prod(v.aval.shape)) for eqn in _walk(jaxpr)
                for v in eqn.outvars
                if len(getattr(v.aval, "shape", ())) >= 2
                and v.aval.shape[-1] == v.aval.shape[-2]
                and v.aval.shape[-1] in squares), default=0)


class TestSwinModel:
    def test_swin_tiny_forward(self):
        from deeplearning_tpu.core.registry import MODELS
        model = MODELS.build("swin_tiny_patch4_window7_224", num_classes=10,
                             patch_size=2, dtype=jnp.float32)
        x = jnp.zeros((2, 112, 112, 3))
        params = model.init(jax.random.key(0), x, train=False)["params"]
        out = model.apply({"params": params}, x, train=False)
        assert out.shape == (2, 10)
        assert np.all(np.isfinite(np.asarray(out)))

    def test_swin_v2_forward(self):
        from deeplearning_tpu.core.registry import MODELS
        model = MODELS.build("swinv2_tiny_patch4_window7_224", num_classes=10,
                             patch_size=2, dtype=jnp.float32)
        x = jnp.zeros((2, 112, 112, 3))
        params = model.init(jax.random.key(0), x, train=False)["params"]
        out = model.apply({"params": params}, x, train=False)
        assert out.shape == (2, 10)
        assert np.all(np.isfinite(np.asarray(out)))

    def test_swin_fused_path_matches_lax_path(self, monkeypatch):
        """The model through the one selector, on the fused path (kernels
        interpreted) and on the lax path: loss and every gradient. 28 and 14
        token grids: shifted and unshifted blocks, head width 16."""
        model = _micro()
        x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 56, 56, 3)),
                        jnp.float32)
        params = model.init(jax.random.key(0), x, train=False)["params"]

        def loss(p):
            return jnp.sum(model.apply({"params": p}, x, train=False) ** 2)

        taken = []

        def run(path):
            monkeypatch.setattr(pwa, "select_path",
                                lambda *_: taken.append(path) or path)
            return jax.jit(jax.value_and_grad(loss))(params)

        (l_lax, g_lax), (l_fused, g_fused) = run("lax"), run("fused")
        assert taken.count("lax") == taken.count("fused") == 4
        np.testing.assert_allclose(float(l_fused), float(l_lax), rtol=1e-5)
        flat_lax = jax.tree_util.tree_leaves_with_path(g_lax)
        flat_fused = jax.tree_util.tree_leaves_with_path(g_fused)
        assert any("relative_position_bias_table" in jax.tree_util.keystr(k)
                   for k, _ in flat_fused)
        for (key, a), (_, b) in zip(flat_fused, flat_lax):
            scale = float(jnp.max(jnp.abs(b))) + 1e-6
            np.testing.assert_allclose(
                np.asarray(a) / scale, np.asarray(b) / scale, atol=2e-4,
                err_msg=jax.tree_util.keystr(key))

    def test_fused_gradient_keeps_scores_out_of_hbm(self, monkeypatch):
        """Outside a ``pallas_call`` the gradient of a v1 ``WindowAttention``
        on the fused path has no ``N x N``-trailing array of BW*heads*N*N
        elements or more; on the lax path the same walk finds them."""
        bw, n, c, heads = 256, 49, 64, 2
        attn = swin.WindowAttention(c, 7, heads, dtype=jnp.float32)
        x = jnp.ones((bw, n, c), jnp.float32)
        mask = jnp.asarray(wu.shift_window_mask(14, 14, 7, 3))
        params = attn.init(jax.random.key(0), x, mask)

        def largest(path):
            monkeypatch.setattr(pwa, "select_path", lambda *_: path)
            jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(
                attn.apply(p, x, mask) ** 2), argnums=(0, 1)))(params, x)
            assert any(e.primitive.name == "pallas_call"
                       for e in _walk(jaxpr.jaxpr)) == (path == "fused")
            return _largest_square_output(jaxpr.jaxpr, n)

        assert largest("lax") >= bw * heads * n * n
        # what is left: the combined bias + mask and the bias gradient
        tiled = max(mask.shape[0],
                    pwa.windows_per_program(bw, mask.shape[0], heads))
        assert 0 < largest("fused") <= tiled * heads * 64 * 64
        assert largest("fused") < bw * heads * n * n

    def test_selector_and_flight_event(self, monkeypatch):
        """v2 and the CPU backend take the lax path, v1 where kernels compile
        the fused one; the flight ring names the path a layer took, once per
        shape, with its blocks."""
        assert jax.default_backend() == "cpu"
        assert pwa.select_path(v2=False) == "lax"
        assert pwa.select_path(v2=True) == "lax"
        monkeypatch.setattr(pwa, "interpret_mode", lambda: False)
        assert pwa.select_path(v2=False) == "fused"
        assert pwa.select_path(v2=True) == "lax"
        # model.init runs the layer once, eagerly: no kernel for that
        assert pwa.select_path(v2=False, initializing=True) == "lax"
        monkeypatch.undo()

        recorder = flight.FlightRecorder()
        monkeypatch.setattr(flight, "_RECORDER", recorder)
        model = _micro()
        x = jnp.zeros((2, 56, 56, 3))
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.key(0), x, train=False))
        events = recorder.events("kernel")
        # 28x28 and 14x14 token grids, one shifted block each
        assert len(events) == 4
        assert all(e["name"] == "window_attention" and e["path"] == "lax"
                   and e["interface"] is None and e["calls"] == 1
                   for e in events)
        assert sorted(m for e in events for m in e["members"]) == [
            f"stage{s}_block{b}/attn" for s in (0, 1) for b in (0, 1)]
        assert {(tuple(e["shape"]), e["masked"]) for e in events} == {
            ((32, 49, 2, 16), False), ((32, 49, 2, 16), True),
            ((8, 49, 4, 16), False), ((8, 49, 4, 16), True)}
        # a second trace bumps the same events: the ring does not grow
        monkeypatch.setattr(pwa, "select_path", lambda *_: "fused")
        jax.eval_shape(lambda p: model.apply(p, x, train=False), shapes)
        events = recorder.events("kernel")
        assert len(events) == 8 and recorder.recorded == 8
        fused = [e for e in events if e["path"] == "fused"]
        assert sum(len(e["members"]) for e in fused) == 4
        # the rows the kernels read and write: the order XLA keeps on the chip
        assert all(e["interface"] == "token_major" for e in fused)
        jax.eval_shape(lambda p: model.apply(p, x, train=False), shapes)
        assert recorder.recorded == 8
        assert all(e["calls"] == 2 for e in recorder.events("kernel")
                   if e["path"] == "fused")
