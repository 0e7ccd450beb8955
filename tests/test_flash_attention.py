"""Pallas flash attention vs lax reference — the TPU-era analog of the Swin
CUDA kernel unit test (swin kernels/window_process/unit_test.py): fused
kernel forward AND backward compared numerically against the naive path.
Runs in Pallas interpret mode on CPU."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_tpu.analysis import jaxpr as audit
from deeplearning_tpu.ops.pallas import flash_attention as fa


def reference_attention(q, k, v, causal=False, kv_len=None):
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q * scale, k).astype(jnp.float32)
    n = q.shape[2]
    if kv_len is not None:
        mask = jnp.arange(n)[None, :] < kv_len
        s = jnp.where(mask[None, None], s, -jnp.inf)
    if causal:
        cm = jnp.tril(jnp.ones((n, n), bool))
        s = jnp.where(cm[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    """Force pallas interpret mode on CPU."""
    import jax.experimental.pallas as pl
    orig = pl.pallas_call
    monkeypatch.setattr(pl, "pallas_call",
                        functools.partial(orig, interpret=True))
    yield


def rand_qkv(b=2, h=3, n=197, d=64, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(0, 1, (b, h, n, d)), dtype)
    return mk(), mk(), mk()


class TestFlashForward:
    def test_matches_reference_f32(self):
        q, k, v = rand_qkv(n=197)
        out = fa.flash_attention(q, k, v)
        ref = reference_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_matches_reference_small_n(self):
        q, k, v = rand_qkv(n=49, d=32)   # swin window size
        out = fa.flash_attention(q, k, v)
        ref = reference_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_causal(self):
        q, k, v = rand_qkv(n=128, d=32)
        out = fa.flash_attention(q, k, v, causal=True)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_bf16(self):
        q, k, v = rand_qkv(n=256, dtype=jnp.bfloat16)
        out = fa.flash_attention(q, k, v)
        ref = reference_attention(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=2e-2, rtol=2e-2)


class TestFlashBackward:
    def test_grads_match_reference(self):
        q, k, v = rand_qkv(b=1, h=2, n=197, d=64)

        def loss_flash(q, k, v):
            return jnp.sum(jnp.square(fa.flash_attention(q, k, v)))

        def loss_ref(q, k, v):
            return jnp.sum(jnp.square(reference_attention(q, k, v)))

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for gf, gr, name in zip(g_flash, g_ref, "qkv"):
            np.testing.assert_allclose(
                np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4,
                err_msg=f"grad mismatch for {name}")

    def test_causal_grads(self):
        q, k, v = rand_qkv(b=1, h=1, n=128, d=32)

        def loss_flash(q, k, v):
            return jnp.sum(fa.flash_attention(q, k, v, causal=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for gf, gr in zip(g_flash, g_ref):
            np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                       atol=5e-4, rtol=5e-4)


class TestChunkGrads:
    def test_single_chunk_equals_full_gradient(self):
        # flash_chunk_grads with the GLOBAL lse/delta over one chunk that
        # IS the whole sequence must equal the full attention gradient
        from deeplearning_tpu.ops.pallas.flash_attention import (
            flash_attention_with_lse, flash_chunk_grads)
        rng = np.random.default_rng(7)
        b, h, n, d = 1, 2, 96, 16      # not a block multiple → padded
        q, k, v, do = (jnp.asarray(rng.normal(0, 1, (b, h, n, d)),
                                   jnp.float32) for _ in range(4))
        out, lse = flash_attention_with_lse(q, k, v)
        delta = jnp.sum(do * out, axis=-1)
        dq, dk, dv = flash_chunk_grads(q, k, v, do, lse, delta)

        def ref_loss(q, k, v):
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (d ** -0.5)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", p, v) * do)

        rq, rk, rv = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(rq),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(rk),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(rv),
                                   atol=1e-4, rtol=1e-4)


def _count_pallas_calls(traced) -> int:
    """``pallas_call`` equations of a traced function, those inside its
    equations' own jaxprs (``checkpoint``, ``custom_vjp_call``) included."""
    return sum(e.primitive.name == "pallas_call"
               for e in audit.iter_eqns(traced))


class TestKeptAcrossRemat:
    """Under a ``jax.checkpoint`` whose policy saves the two names
    ``_flash_fwd`` places (the decoder skeleton's ``remat_block``), the
    backward pass holds the two backward kernels and no second forward
    kernel; the gradients are the plain checkpoint's bit for bit."""

    # a call's keywords and its key/value heads under 3 query heads
    CASES = {
        "causal": (dict(causal=True), 3),
        "windowed": (dict(causal=True, window=40), 3),
        "grouped": (dict(causal=True, window=40), 1),
    }

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_forward_kernel_runs_once(self, case):
        from deeplearning_tpu.models.language import decoder
        kwargs, kv_heads = self.CASES[case]
        q, k, v = rand_qkv(b=1, h=3, n=128, d=32, seed=3)
        k, v = k[:, :kv_heads], v[:, :kv_heads]

        def loss(q, k, v):
            out = fa.flash_attention(q, k, v, block_q=64, block_k=64,
                                     **kwargs)
            return jnp.sum(jnp.square(out))

        grads = {}
        for name, fn, calls in (
                ("kept", jax.checkpoint(
                    loss, policy=decoder.KEEP_ATTENTION_CORE), 3),
                ("plain", jax.checkpoint(loss), 4)):
            grad = jax.grad(fn, argnums=(0, 1, 2))
            assert _count_pallas_calls(
                jax.make_jaxpr(grad)(q, k, v)) == calls, name
            grads[name] = grad(q, k, v)
        for kept, plain in zip(grads["kept"], grads["plain"]):
            np.testing.assert_array_equal(np.asarray(kept),
                                          np.asarray(plain))

    def test_logsumexp_residual_is_compact(self):
        q, k, v = rand_qkv(b=2, h=3, n=128, d=32)
        _, res = fa._flash_fwd(q, k, v, 32 ** -0.5, 128, True, 64, 64)
        assert res[4].shape == (6, 128) and res[4].dtype == jnp.float32


class TestCausalCoreAtHeadWidth64:
    """The decoder's causal kernels at a head width of 64, half a lane tile
    (Granite 4.0-H's NoPE attention: 32 query heads over 8 key/value heads,
    scores times 0.015625), interpreted, against ``causal_attention_lax``:
    forward, ``dq``, ``dk`` and ``dv``, blocks smaller than the sequence so
    that the causal key loop's end matters. float32 throughout, so the kernels
    differ from the oracle by the order of their sums alone: 1e-5 of the
    largest output, 1e-4 of the largest gradient."""

    @pytest.mark.parametrize("heads", [(4, 4), (8, 2)], ids=["h4kv4", "h8kv2"])
    def test_kernels_match_the_lax_oracle(self, monkeypatch, heads):
        monkeypatch.setattr(fa, "CAUSAL_BLOCK_Q", 32)
        monkeypatch.setattr(fa, "CAUSAL_BLOCK_K", 16)
        h, kv = heads
        q = rand_qkv(b=1, h=h, n=128, d=64, seed=5)[0]
        k, v = (x[:, :kv] for x in rand_qkv(b=1, h=h, n=128, d=64, seed=6)[1:])

        def loss(path):
            return lambda q, k, v: jnp.sum(jnp.sin(fa.causal_attention(
                q, k, v, 0.015625, path)))
        out = fa.causal_attention(q, k, v, 0.015625, "fused")
        want = fa.causal_attention(q, k, v, 0.015625, "lax")
        assert out.shape == (1, h, 128, 64)
        assert float(jnp.abs(out - want).max()) <= 1e-5 * float(
            jnp.abs(want).max())
        for a, b in zip(jax.grad(loss("fused"), (0, 1, 2))(q, k, v),
                        jax.grad(loss("lax"), (0, 1, 2))(q, k, v)):
            assert a.shape == b.shape
            assert float(jnp.abs(a - b).max()) <= 1e-4 * float(
                jnp.abs(b).max())

    def test_the_path_takes_half_tile_heads(self, monkeypatch):
        monkeypatch.setattr(fa, "interpret_mode", lambda: False)
        assert fa.select_path(8192, 64) == "fused"
        assert fa.select_path(8192, 32) == "lax"
        assert fa.select_path(8192, 96) == fa.select_path(8192, 192) == "lax"
        # the accepted cells' widths keep their path
        assert fa.select_path(4096, 256) == fa.select_path(8192, 128) \
            == "fused"
