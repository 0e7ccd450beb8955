"""Global attention: fused Pallas kernels vs the lax path + the ViT model.

On the CPU backend the kernels run in interpret mode
(``common.interpret_mode``) and the model takes the lax path; the tests that
drive the model through the fused path patch the one selector,
``global_attention.select_path``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_tpu.core.registry import MODELS
from deeplearning_tpu.models.classification import vit
from deeplearning_tpu.obs import flight
from deeplearning_tpu.ops.pallas import global_attention as ga


def _inputs(b, n, heads, d, dtype=jnp.float32, seed=0):
    """qkv rows as the qkv matmul writes them and a cotangent for the
    output."""
    rng = np.random.default_rng(seed)
    qkv = jnp.asarray(rng.normal(0, 1.0, (b, n, 3 * heads * d)), dtype)
    weight = jnp.asarray(rng.normal(size=(b, n, heads * d)), dtype)
    return qkv, weight


def _reference(qkv, heads):
    b, n, c3 = qkv.shape
    x = qkv.reshape(b, n, 3, heads, c3 // 3 // heads)
    return vit.dot_product_attention(
        x[:, :, 0], x[:, :, 1], x[:, :, 2]).reshape(b, n, c3 // 3)


# (batch, tokens, heads, head width): four images a program at most, so no
# batch here is a multiple of the block but the last
KERNEL_CASES = {
    "vit_b16_ragged_batch": (5, 197, 12, 64),
    "patch32_50_tokens": (3, 50, 12, 64),
    "micro_d32": (6, 17, 4, 32),
    "odd_head": (2, 197, 3, 64),
}


class TestPallasGlobalAttention:
    @pytest.mark.parametrize("case", sorted(KERNEL_CASES))
    def test_fused_matches_reference(self, case):
        b, n, heads, d = KERNEL_CASES[case]
        qkv, _ = _inputs(b, n, heads, d)
        out = ga.global_attention(qkv, heads=heads)
        ref = _reference(qkv, heads)
        assert out.shape == ref.shape == (b, n, heads * d)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("case", sorted(KERNEL_CASES))
    def test_fused_backward_matches_reference(self, case):
        """dq, dk and dv of the fused backward kernel against autodiff
        through the lax path."""
        b, n, heads, d = KERNEL_CASES[case]
        qkv, weight = _inputs(b, n, heads, d)
        fused = jax.grad(lambda a: jnp.sum(
            ga.global_attention(a, heads=heads) * weight))(qkv)
        ref = jax.grad(lambda a: jnp.sum(_reference(a, heads) * weight))(qkv)
        assert fused.shape == ref.shape
        for name, a, b_ in zip(("dq", "dk", "dv"),
                               jnp.split(fused, 3, axis=-1),
                               jnp.split(ref, 3, axis=-1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=5e-5, rtol=5e-5, err_msg=name)

    @pytest.mark.parametrize("case", sorted(KERNEL_CASES))
    def test_bfloat16_is_as_close_to_float32_as_the_lax_path(self, case):
        """In bfloat16 both paths round q*scale, p and the outputs alike, so
        the kernel's distance to the float32 result is the lax path's, output
        and gradient (a factor two of room: the sums differ in order)."""
        b, n, heads, d = KERNEL_CASES[case]
        qkv, weight = _inputs(b, n, heads, d, jnp.bfloat16)

        def both(attend, x, w):
            out, vjp = jax.vjp(lambda a: attend(a, heads), x)
            return out.astype(jnp.float32), vjp(w)[0].astype(jnp.float32)

        exact = both(_reference, qkv.astype(jnp.float32),
                     weight.astype(jnp.float32))
        lax = both(_reference, qkv, weight)
        fused = both(lambda a, h: ga.global_attention(a, heads=h), qkv,
                     weight)
        assert fused[0].shape == (b, n, heads * d)
        for name, f, l, e in zip(("out", "dqkv"), fused, lax, exact):
            gap = lambda x: float(jnp.sqrt(jnp.mean((x - e) ** 2)))  # noqa: E731,E501
            assert gap(f) <= 2 * gap(l) + 1e-6, (name, gap(f), gap(l))
            np.testing.assert_allclose(np.asarray(f), np.asarray(l),
                                       atol=0.06 * float(jnp.max(jnp.abs(e))),
                                       err_msg=name)

    def test_vmap_folds_the_mapped_axis_into_the_images(self):
        qkv, weight = _inputs(6, 17, 4, 32)
        qkv, weight = (x.reshape((3, 2) + x.shape[1:]) for x in (qkv, weight))

        def grad(a, w):
            return jax.grad(lambda t: jnp.sum(
                ga.global_attention(t, heads=4) * w))(a)

        np.testing.assert_array_equal(
            np.asarray(jax.vmap(grad)(qkv, weight)),
            np.stack([np.asarray(grad(a, w)) for a, w in zip(qkv, weight)]))

    @pytest.mark.parametrize("b,n,c,itemsize,want", [
        (128, 197, 768, 2, 4), (128, 197, 768, 4, 2), (2, 197, 768, 2, 2),
        (128, 50, 768, 2, 4), (64, 197, 1024, 2, 3), (1, 17, 128, 4, 1)])
    def test_images_per_program_from_shapes(self, b, n, c, itemsize, want):
        assert ga.images_per_program(b, n, c, itemsize) == want


def _tiny(**kw):
    return MODELS.build("vit_micro_patch4_56", num_classes=10,
                        dtype=jnp.float32, **kw)


def _walk(jaxpr):
    """Every equation outside a ``pallas_call``, sub-jaxprs included (the
    kernels' own primitives are opaque: they lower to one or to the lax
    path)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def _square_outputs(jaxpr, n):
    """Shapes of the equation outputs whose trailing shape is ``n x n`` or
    one of the kernels' padded sizes of it."""
    sizes = {n, ga._key_rows(n), ga._query_lanes(n), 2 * ga._query_lanes(n)}
    return {tuple(v.aval.shape) for eqn in _walk(jaxpr) for v in eqn.outvars
            if len(getattr(v.aval, "shape", ())) >= 2
            and v.aval.shape[-1] in sizes and v.aval.shape[-2] in sizes}


def _force(monkeypatch, path, taken=None):
    def select(tokens, head_width, **seen):
        if taken is not None:
            taken.append((path, tokens, head_width, seen))
        return path
    monkeypatch.setattr(ga, "select_path", select)


class TestVisionTransformer:
    def test_fused_path_matches_lax_path(self, monkeypatch):
        """The model through the one selector, on the fused path (kernels
        interpreted) and on the lax path: loss and every gradient. 197
        tokens, four heads of width 32, three images (a ragged block)."""
        model = _tiny()
        x = jnp.asarray(np.random.default_rng(0).normal(size=(3, 56, 56, 3)),
                        jnp.float32)
        params = model.init(jax.random.key(0), x, train=False)["params"]

        def loss(p):
            return jnp.sum(model.apply({"params": p}, x, train=False) ** 2)

        taken = []

        def run(path):
            _force(monkeypatch, path, taken)
            return jax.jit(jax.value_and_grad(loss))(params)

        (l_lax, g_lax), (l_fused, g_fused) = run("lax"), run("fused")
        assert [t[0] for t in taken] == ["lax"] * 6 + ["fused"] * 6
        assert all(t[1:3] == (197, 32) for t in taken)
        np.testing.assert_allclose(float(l_fused), float(l_lax), rtol=1e-5)
        flat_lax = jax.tree_util.tree_leaves_with_path(g_lax)
        flat_fused = jax.tree_util.tree_leaves_with_path(g_fused)
        assert len(flat_fused) == len(flat_lax) > 6 * 8
        for (key, a), (_, b) in zip(flat_fused, flat_lax):
            scale = float(jnp.max(jnp.abs(b))) + 1e-6
            np.testing.assert_allclose(
                np.asarray(a) / scale, np.asarray(b) / scale, atol=2e-4,
                err_msg=jax.tree_util.keystr(key))

    def test_no_score_array_is_left_in_the_vit_b16_train_step(
            self, monkeypatch):
        """Outside the two kernels' calls the gradient of ViT-B/16's loss on
        the fused path holds no array with a 197 x 197 trailing shape (nor one
        of the kernels' padded sizes); on the lax path the same walk finds
        the scores, ``(B, 12, 197, 197)``."""
        model = MODELS.build("vit_base_patch16_224", num_classes=1000)
        x = jnp.zeros((2, 224, 224, 3), jnp.float32)
        params = jax.eval_shape(
            lambda: model.init(jax.random.key(0), x, train=False))

        def squares(path):
            _force(monkeypatch, path)
            jaxpr = jax.make_jaxpr(jax.grad(lambda p: jnp.sum(
                model.apply(p, x, train=True,
                            rngs={"dropout": jax.random.key(1)}) ** 2)))(
                params)
            calls = [e.primitive.name for e in _walk(jaxpr.jaxpr)
                     if e.primitive.name.startswith("global_attention")]
            assert sorted(set(calls)) == (
                ["global_attention_backward", "global_attention_forward"]
                if path == "fused" else [])
            assert len(calls) == (24 if path == "fused" else 0)
            return _square_outputs(jaxpr.jaxpr, 197)

        assert (2, 12, 197, 197) in squares("lax")
        assert squares("fused") == set()

    @pytest.mark.parametrize("seen,want", [
        (dict(tokens=197, head_width=64), "fused"),
        (dict(tokens=50, head_width=32), "fused"),
        (dict(tokens=256, head_width=64), "fused"),
        # ViT-H/14's heads, a 384 px image's 577 tokens: not covered
        (dict(tokens=257, head_width=80), "lax"),
        (dict(tokens=577, head_width=64), "lax"),
        (dict(tokens=197, head_width=64, dropout=True), "lax"),
        (dict(tokens=197, head_width=64, injected=True), "lax"),
        (dict(tokens=197, head_width=64, initializing=True), "lax"),
    ])
    def test_selector(self, monkeypatch, seen, want):
        assert jax.default_backend() == "cpu"
        assert ga.select_path(**seen) == "lax"      # kernels would interpret
        monkeypatch.setattr(ga, "interpret_mode", lambda: False)
        assert ga.select_path(**seen) == want

    def test_layer_tells_the_selector_what_it_sees(self, monkeypatch):
        """``Attention`` hands the selector its tokens, head width, whether
        dropout will be drawn, whether an ``attn_fn`` was injected and
        whether ``model.init`` is running; an injected function keeps its
        slot and dropout its draw."""
        taken = []
        _force(monkeypatch, "lax", taken)
        x = jnp.zeros((2, 56, 56, 3))
        calls = []

        def injected(q, k, v, **kw):
            calls.append(kw["dropout_rate"])
            return vit.dot_product_attention(q, k, v, **kw)

        model = _tiny(depth=1, attn_drop_rate=0.1, attn_fn=injected)
        params = model.init(jax.random.key(0), x, train=False)
        model.apply(params, x, train=True, rngs={"dropout": jax.random.key(1)})
        model.apply(params, x, train=False)
        assert [t[3] for t in taken] == [
            dict(dropout=False, injected=True, initializing=True),
            dict(dropout=True, injected=True, initializing=False),
            dict(dropout=False, injected=True, initializing=False)]
        assert calls == [0.1] * 3

    def test_flight_ring_names_the_blocks_of_a_vit_b16_trace(
            self, monkeypatch):
        recorder = flight.FlightRecorder()
        monkeypatch.setattr(flight, "_RECORDER", recorder)
        model = MODELS.build("vit_base_patch16_224", num_classes=1000)
        x = jnp.zeros((128, 224, 224, 3))
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.key(0), x, train=False))
        monkeypatch.setattr(ga, "interpret_mode", lambda: False)
        for _ in range(2):     # a second trace bumps the same event
            jax.eval_shape(lambda p: model.apply(p, x, train=True), shapes)
        lax, fused = recorder.events("kernel")
        assert recorder.recorded == 2
        assert lax["path"] == "lax" and lax["calls"] == 12      # model.init
        assert fused["name"] == "attention" and fused["path"] == "fused"
        assert fused["shape"] == [128, 197, 12, 64] and fused["calls"] == 24
        assert fused["members"] == lax["members"] == [
            f"blocks_{i}/attn" for i in range(12)]


class TestPartitioning:
    def test_program_over_a_mesh_lowers_to_the_lax_mathematics(
            self, monkeypatch):
        """A Mosaic kernel cannot be partitioned automatically, so in a
        program that GSPMD spreads over a 4-device data-parallel mesh the
        fused layer's two calls lower to the lax mathematics: nothing is
        gathered, the batch stays sharded and the numbers are the lax
        path's to the bit; the same layer in a one-device program runs the
        kernels (close, not equal)."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
        attn = vit.Attention(num_heads=4, dtype=jnp.float32)
        x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 50, 128)),
                        jnp.float32)
        params = attn.init(jax.random.key(0), x)
        grad = jax.grad(lambda p, a: jnp.sum(attn.apply(p, a) ** 2),
                        argnums=(0, 1))

        def compiled(path, **shardings):
            _force(monkeypatch, path)
            return jax.jit(grad, **shardings).lower(params, x).compile()

        over_mesh = dict(in_shardings=(NamedSharding(mesh, P()),
                                       NamedSharding(mesh, P("data"))))
        lax, fused = compiled("lax", **over_mesh), compiled("fused",
                                                            **over_mesh)
        text = fused.as_text()
        assert "all-gather" not in text and "all-to-all" not in text
        g_lax, g_fused = lax(params, x), fused(params, x)
        assert g_fused[1].sharding.spec == P("data")
        one_device = compiled("fused")(params, x)
        for a, b, c in zip(*map(jax.tree.leaves,
                                (g_fused, g_lax, one_device))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            np.testing.assert_allclose(np.asarray(c), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)
        assert any(not np.array_equal(np.asarray(c), np.asarray(b))
                   for b, c in zip(jax.tree.leaves(g_lax),
                                   jax.tree.leaves(one_device)))
