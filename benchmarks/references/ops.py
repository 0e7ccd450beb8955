"""Plain building blocks shared by the references: the matrix product in a
stated precision, layer norm, GELU. Imports nothing of the program.

``mode`` is how every matrix product of a reference is computed:

- ``f32``  float32 operands at ``highest`` (six bf16 passes on a TPU): the
           reference proper;
- ``bf16`` bfloat16 operands, float32 accumulation: what the configurations
           state the program computes in (used by self-checks only);
- ``fp8``  operands scaled per tensor to the format's range and rounded to
           ``float8_e4m3fn`` (gradients flowing back: ``float8_e5m2``),
           float32 accumulation, in the forward product and in both products
           of its backward pass: the control, the next precision below bf16,
           as a later PR would be tempted to train in it.

Everything that is not a matrix product (layer norm, softmax, GELU, the loss,
the optimizer) is float32 in every mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

MODES = ("f32", "bf16", "fp8")
_FP8 = {"e4m3": (jnp.float8_e4m3fn, 448.0), "e5m2": (jnp.float8_e5m2, 57344.0)}


def _round_fp8(x, kind: str):
    """x rounded to an fp8 format after scaling its largest entry to the
    format's largest number, and scaled back (float32 holding fp8 values)."""
    dtype, top = _FP8[kind]
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def _bf16_product(spec, a, b):
    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(spec, a, b):
    # fp8 values are exact in bfloat16, so a bf16 product of them is the fp8 one
    return _bf16_product(spec, _round_fp8(a, "e4m3"), _round_fp8(b, "e4m3"))


def _fp8_fwd(spec, a, b):
    qa, qb = _round_fp8(a, "e4m3"), _round_fp8(b, "e4m3")
    return _bf16_product(spec, qa, qb), (qa, qb)


def _fp8_bwd(spec, saved, g):
    _, back = jax.vjp(functools.partial(_bf16_product, spec), *saved)
    return back(_round_fp8(g, "e5m2"))


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


def einsum(spec: str, a, b, mode: str):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if mode == "f32":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    if mode == "bf16":
        return _bf16_product(spec, a, b)
    if mode == "fp8":
        return _fp8_einsum(spec, a, b)
    raise ValueError(f"mode {mode!r}; one of {MODES}")


def dense(x, p, mode: str):
    y = einsum("...i,io->...o", x, p["kernel"], mode)
    if "bias" in p:
        y = y + p["bias"]
    return y


def layer_norm(x, p, eps: float = 1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x * x * x)))


def mlp(x, p, mode: str):
    return dense(gelu_tanh(dense(x, p["fc1"], mode)), p["fc2"], mode)


def attention(q, k, v, mode: str, bias=None):
    """softmax(q k^T / sqrt(d) + bias) v over (..., tokens, heads, d)."""
    scale = q.shape[-1] ** -0.5
    s = einsum("...qhd,...khd->...hqk", q * scale, k, mode)
    if bias is not None:
        s = s + bias
    return einsum("...hqk,...khd->...qhd", jax.nn.softmax(s, axis=-1), v, mode)


def dense_spec(n_in: int, n_out: int, bias: bool = True) -> dict:
    spec = {"kernel": ((n_in, n_out), "normal")}
    if bias:
        spec["bias"] = ((n_out,), "zeros")
    return spec


def norm_spec(n: int) -> dict:
    return {"scale": ((n,), "ones"), "bias": ((n,), "zeros")}
