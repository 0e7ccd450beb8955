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


def _vjp_case(b, h, kv, n, d, sm_scale=None, causal=True, **keywords):
    """The output and the gradients of ``sum(sin(attention))`` in q, k and v
    through ``flash_attention`` and through the plain mathematics in float32:
    causal (with ``window`` if given), ``causal_attention_lax``; else a
    softmax over every key, the kernels' keys padded past ``n`` to a whole
    block."""
    q = rand_qkv(b=b, h=h, n=n, d=d, seed=1)[0]
    k, v = (x[:, :kv] for x in rand_qkv(b=b, h=h, n=n, d=d, seed=2)[1:])
    scale = d ** -0.5 if sm_scale is None else sm_scale

    def fused(q, k, v):
        return fa.flash_attention(q, k, v, sm_scale=scale, causal=causal,
                                  **keywords)

    def oracle(q, k, v):
        if causal:
            return fa.causal_attention_lax(q, k, v, scale,
                                           keywords.get("window"))
        return reference_attention(q, k, v)

    def results(attention):
        grads = jax.grad(lambda *qkv: jnp.sum(jnp.sin(attention(*qkv))),
                         (0, 1, 2))(q, k, v)
        return dict(zip(("out", "dq", "dk", "dv"),
                        (attention(q, k, v), *grads)))
    return results(fused), results(oracle)


def _chunk_case():
    """``flash_chunk_grads`` over one chunk that is the whole sequence (96
    tokens: padded to a block), with the GLOBAL logsumexp and delta, against
    the full attention's gradient; its gradients come back in float32."""
    rng = np.random.default_rng(7)
    b, h, n, d = 1, 2, 96, 16
    q, k, v, do = (jnp.asarray(rng.normal(0, 1, (b, h, n, d)), jnp.float32)
                   for _ in range(4))
    out, lse = fa.flash_attention_with_lse(q, k, v)
    grads = fa.flash_chunk_grads(q, k, v, do, lse, jnp.sum(do * out, -1))
    assert all(g.dtype == jnp.float32 for g in grads)
    names = ("dq", "dk", "dv")

    def ref_loss(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (d ** -0.5)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", p, v) * do)
    return (dict(zip(names, grads)),
            dict(zip(names, jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v))))


# blocks smaller than the sequence, query blocks and key blocks of different
# sizes both ways, so that each key block's run of query blocks and the dQ
# sum across key blocks matter
BACKWARD_CASES = {
    "causal": functools.partial(_vjp_case, 1, 4, 4, 128, 32, block_q=32,
                                block_k=16),
    "causal_key_blocks_wider": functools.partial(
        _vjp_case, 1, 2, 2, 128, 32, block_q=16, block_k=32),
    # a window of 40 ends inside a block of 16 and of 32
    "window_mid_block": functools.partial(
        _vjp_case, 1, 4, 4, 128, 32, window=40, block_q=32, block_k=16),
    # Granite 4.0-H's core: heads of 64, 4 query heads a key/value head,
    # scores times 0.015625
    "grouped_g4_width64": functools.partial(
        _vjp_case, 1, 8, 2, 128, 64, sm_scale=0.015625, block_q=32,
        block_k=16),
    # Mellum2's sliding core: 8 query heads a key/value head, a window
    "grouped_g8_window": functools.partial(
        _vjp_case, 1, 8, 1, 128, 32, window=40, block_q=32, block_k=32),
    # not causal, 197 tokens: 59 padded keys masked by ``kv_len``
    "padded_keys": functools.partial(_vjp_case, 1, 2, 2, 197, 64,
                                     causal=False),
    "chunk_grads_f32": _chunk_case,
}


@pytest.mark.parametrize("case", sorted(BACKWARD_CASES))
def test_the_backward_kernel_matches_the_oracle(case):
    """The one backward kernel's dq, dk and dv (and the forward kernel's
    output), interpreted, against the plain mathematics: float32 throughout,
    so the two differ by the order of their sums alone, within 1e-5 of the
    largest value."""
    kernel, oracle = BACKWARD_CASES[case]()
    assert kernel.keys() == oracle.keys()
    for name, b in oracle.items():
        a = kernel[name]
        assert a.shape == b.shape, name
        assert float(jnp.abs(a - b).max()) <= 1e-5 * float(
            jnp.abs(b).max()), name


def _count_pallas_calls(traced) -> int:
    """``pallas_call`` equations of a traced function, those inside its
    equations' own jaxprs (``checkpoint``, ``custom_vjp_call``) included."""
    return sum(e.primitive.name == "pallas_call"
               for e in audit.iter_eqns(traced))


class TestKeptAcrossRemat:
    """Under a ``jax.checkpoint`` whose policy saves the two names
    ``_flash_fwd`` places (the decoder skeleton's ``remat_block``), the
    backward pass holds the one backward kernel and no second forward
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
                    loss, policy=decoder.KEEP_ATTENTION_CORE), 2),
                ("plain", jax.checkpoint(loss), 3)):
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
    (Granite 4.0-H's NoPE attention; its gradients are a case of
    ``test_the_backward_kernel_matches_the_oracle``)."""

    def test_the_path_takes_half_tile_heads(self, monkeypatch):
        monkeypatch.setattr(fa, "interpret_mode", lambda: False)
        assert fa.select_path(8192, 64) == "fused"
        assert fa.select_path(8192, 32) == "lax"
        assert fa.select_path(8192, 96) == fa.select_path(8192, 192) == "lax"
        # the accepted cells' widths keep their path
        assert fa.select_path(4096, 256) == fa.select_path(8192, 128) \
            == "fused"
