"""Fused global (all-to-all) attention, forward and backward — ViT's score
path kept in VMEM.

The lax path (``models/classification/vit.py::dot_product_attention``) is two
einsums round a float32 softmax: XLA writes the ``(B, heads, N, N)`` scores
to HBM, reads them back for the softmax, keeps the probabilities for the
backward pass and walks them three more times there, at a 197-wide minor
dimension that pads to 256 lanes and a head width that half-fills them. Here
``QK^T*scale -> softmax -> PV`` runs per block of whole images in VMEM, and
so does its backward: one ``jax.custom_vjp`` whose backward kernel recomputes
the softmax from q and k and emits ``dqkv``. No array with an ``N x N``
trailing shape reaches HBM in either direction and nothing but ``qkv`` is
saved for the backward pass.

Layout, as ``window_attention`` (whose arithmetic helpers this module
imports; the two share no control flow): the kernels read ``qkv`` as the
``(B, N, 3*C)`` rows the qkv matmul wrote and write ``(B, N, C)`` rows, heads
are lane slices, the scores are held transposed and two heads wide,
``(images, keys, 2 * query lanes)``: keys down the rows, so the softmax
reduces across vregs and sublanes, and one MXU pass serves a pair of heads
(at d = 64 the pair's contraction is exactly the v5e MXU's depth). A block
holds ``query lanes`` rows of an image (256 at N = 197): the rows past ``N``
lie outside the array, arrive as whatever the buffer held and are zeroed in
VMEM, as are the images past ``B`` in a ragged last block; as keys (the
first ``key rows``, 208 at N = 197, are contracted) they are masked out of
the softmax by an iota, as queries their output rows are dropped by the
write. Numbers as the lax path has them: ``q*scale`` in the input dtype,
scores and softmax in float32, ``p`` cast to the input dtype for ``PV``,
float32 accumulation.

``select_path`` is the one place that chooses between this and
``dot_product_attention`` (the oracle). These two are all the attention a
ViT block has: the per-head flash kernels of ``flash_attention.py`` are
reached only through an injected ``attn_fn`` (the ring's and Ulysses'
``use_flash``), which turns this module off, and stay for long sequences
until ROADMAP W8 measures one.

Mosaic kernels cannot be partitioned automatically: lowered into a program
that GSPMD spreads over several devices, a bare ``pallas_call`` is refused
("wrap the call in a shard_map"), a ``shard_map`` wants a mesh the layer
cannot see, and this runtime's TPU compiler refuses ``custom_partitioning``
("Custom emitter for CustomSPMDPartitioning not found", PR 29, four chips).
So the two calls are primitives that choose when they are lowered, where the
program's devices can be seen: the kernel in a one-device program (or inside a
``shard_map``), the lax mathematics, which GSPMD partitions as it always
did, in one that spans several. PERF.md §7 has the item.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax._src import sharding_impls
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend.core import Primitive
from jax.interpreters import batching, mlir

from .common import interpret_mode
from .window_attention import (_MASKED, _NN, _NT, _dot, _pair_lanes,
                               _softmax_over_keys, _stack_heads,
                               _unstack_heads, _zero_outside)

# what one program's VMEM holds with room to spare, and what the tests and
# the chip have seen: 197 (224 px at patch 16), 50 (patch 32, MAE's visible
# tokens), 17. Longer sequences want key blocks and an online softmax
MAX_TOKENS = 256
HEAD_WIDTHS = (32, 64)
_VMEM_LIMIT = 96 * 2 ** 20


def select_path(tokens: int, head_width: int, *, dropout: bool = False,
                injected: bool = False, initializing: bool = False) -> str:
    """Which attention core a layer runs, from what the code can see:
    ``"fused"`` where the kernels compile (not the CPU backend, where they
    would run interpreted) and cover the shape (head width 64 or 32, up to
    ``MAX_TOKENS`` tokens); ``"lax"`` (``dot_product_attention`` or the
    injected ``attn_fn``: ring and Ulysses keep their slot) for everything
    else, for attention dropout while training, which the kernels do not
    draw, and while ``model.init`` runs the layer once, eagerly: a kernel
    traced, lowered and loaded for that one call costs set-up seconds and
    nothing is trained or served by it. (What is ``"fused"`` here still
    lowers to the lax mathematics inside a program that spans several
    devices: ``_chosen_at_lowering``.)"""
    covered = head_width in HEAD_WIDTHS and tokens <= MAX_TOKENS
    return ("fused" if covered and not (dropout or injected or initializing
                                        or interpret_mode()) else "lax")


def _query_lanes(n: int) -> int:
    """Lanes one head's ``n`` queries take in the scores, and rows of a
    block: two heads side by side fill whole 128-lane tiles."""
    return 64 if n <= 64 else -(-n // 128) * 128


def _key_rows(n: int) -> int:
    """Rows of a block that are contracted as keys: a whole number of
    bfloat16 tiles."""
    return -(-n // 16) * 16


# images a program takes at most. ViT-B/16 at batch 128 on a v5e, the
# kernels' own device time a layer off the profiler (PR 29), forward |
# backward: 1 image 0.455 | 0.862 ms, 2 images 0.328 | 0.651, 4 images 0.327 |
# 0.648, 8 the same at twice the compile time. Key rows 256 instead of 208,
# the mask on the last two row tiles only, the scores with the queries down
# the rows (no transpose, lane reductions: 0.379 | 0.799) and the next
# pair's QK^T issued before this pair's softmax (0.312 | 0.684) were no
# better. A wall clock round the bare call mostly times XLA's layout copies
# of its operands (0.84 | 1.53 ms): only the profiler's events tell
_IMAGES_PER_PROGRAM = 4


def images_per_program(b: int, n: int, c: int, itemsize: int) -> int:
    """Images a program takes: its blocks (qkv, the output's gradient and
    dqkv, each double-buffered) within a quarter of the VMEM limit."""
    rows = _query_lanes(n) * 7 * c * itemsize * 2
    return max(1, min(b, _IMAGES_PER_PROGRAM, _VMEM_LIMIT // 4 // rows))


def _mask_keys(s, n: int):
    """-1e9 in the rows (keys) past ``n`` of ``(images, keys, queries)``
    scores."""
    if s.shape[1] == n:
        return s
    valid = jax.lax.broadcasted_iota(jnp.int32, (1, s.shape[1], 1), 1) < n
    return jnp.where(valid, s, _MASKED)


def _keys_contracted(p, x):
    """``p^T x`` for ``(images, keys, queries)`` scores and ``(images,
    keys, lanes)`` rows, as ``(x^T p)^T``: the transposes are then of the
    narrow ``x`` and of the ``(lanes, queries)`` product, not of the whole
    score tile, which a product contracting the rows of both would have
    Mosaic transpose (2 % of the backward kernel's time, PR 29)."""
    return jnp.swapaxes(_dot(jnp.swapaxes(x, 1, 2), p, _NN), 1, 2)


# The work of one pair of heads is a jitted function, so that a kernel's
# trace holds it once however many heads there are (window_attention says
# why).

@functools.partial(jax.jit, static_argnames=("n", "d"))
def _pair_forward(q, k, v, n, d):
    q = _stack_heads(q * (d ** -0.5), d)
    p = _softmax_over_keys(_mask_keys(_dot(k, q, _NT), n))
    return _unstack_heads(_keys_contracted(p.astype(v.dtype), v), d)


@functools.partial(jax.jit, static_argnames=("n", "d"))
def _pair_backward(q, k, v, do, n, d):
    """(dq, dk, dv)."""
    scale = d ** -0.5
    q = _stack_heads(q * scale, d)
    do = _stack_heads(do, d)
    p = _softmax_over_keys(_mask_keys(_dot(k, q, _NT), n))
    dv = _dot(p.astype(do.dtype), do, _NN)
    dp = _dot(v, do, _NT)
    ds = (p * (dp - jnp.sum(p * dp, axis=1, keepdims=True))).astype(k.dtype)
    dq = _unstack_heads(_keys_contracted(ds, k), d) * scale
    return dq, _dot(ds, q, _NN), dv


def _pairs(qkv_ref, heads, n, b):
    """(first lane, width, rows zeroed outside the array, q, k, v) of each
    pair of heads in a block of the qkv rows; ``rows(ref_slice)`` zeroes the
    rows past ``n`` and the images past ``b``."""
    ib, _, c3 = qkv_ref.shape
    c = c3 // 3
    kr = _key_rows(n)
    left = b - pl.program_id(0) * ib if b % ib else None
    rows = lambda x: _zero_outside(x, n, left)           # noqa: E731
    for lo, w in _pair_lanes(heads, c // heads):
        yield (lo, w, rows, rows(qkv_ref[:, :, lo:lo + w]),
               *(rows(qkv_ref[:, :kr, at + lo:at + lo + w])
                 for at in (c, 2 * c)))


def _fwd_kernel(qkv_ref, o_ref, *, heads, n, b):
    d = o_ref.shape[2] // heads
    for lo, w, _, q, k, v in _pairs(qkv_ref, heads, n, b):
        o_ref[:, :, lo:lo + w] = _pair_forward(q, k, v, n=n, d=d).astype(
            o_ref.dtype)


def _bwd_kernel(qkv_ref, do_ref, dqkv_ref, *, heads, n, b):
    c = do_ref.shape[2]
    for lo, w, rows, q, k, v in _pairs(qkv_ref, heads, n, b):
        dq, dk, dv = _pair_backward(q, k, v, rows(do_ref[:, :, lo:lo + w]),
                                    n=n, d=c // heads)
        dqkv_ref[:, :, lo:lo + w] = dq.astype(dqkv_ref.dtype)
        # dk and dv have ``_key_rows`` rows: the rest lie outside the array
        for at, grad in ((c, dk), (2 * c, dv)):
            dqkv_ref[:, :grad.shape[1], at + lo:at + lo + w] = grad.astype(
                dqkv_ref.dtype)


def _call(kernel, name, qkv, others, out_width, heads):
    b, n, c3 = qkv.shape
    c = c3 // 3
    ib = images_per_program(b, n, c, qkv.dtype.itemsize)
    rows = lambda width: pl.BlockSpec(           # noqa: E731
        (ib, _query_lanes(n), width), lambda i: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(kernel, heads=heads, n=n, b=b),
        grid=(pl.cdiv(b, ib),),
        in_specs=[rows(c3)] + [rows(c)] * len(others),
        out_specs=rows(out_width),
        out_shape=jax.ShapeDtypeStruct((b, n, out_width), qkv.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret_mode(),
        name=name,
    )(qkv, *others)


# jitted (as ``global_attention`` is) so that a model's blocks share one
# trace and one lowering of their kernel: the step's set-up time
@functools.partial(jax.jit, static_argnames="heads")
def _forward(qkv, heads):
    return _call(_fwd_kernel, "global_attention_fwd", qkv, (),
                 qkv.shape[2] // 3, heads)


@functools.partial(jax.jit, static_argnames="heads")
def _backward(qkv, g, heads):
    return _call(_bwd_kernel, "global_attention_bwd", qkv, (g,),
                 qkv.shape[2], heads)


def _lax_forward(qkv, heads):
    from ...models.classification.vit import dot_product_attention
    b, n, c3 = qkv.shape
    x = qkv.reshape(b, n, 3, heads, c3 // 3 // heads)
    return dot_product_attention(x[:, :, 0], x[:, :, 1], x[:, :, 2]).reshape(
        b, n, c3 // 3)


def _lax_backward(qkv, g, heads):
    return jax.vjp(functools.partial(_lax_forward, heads=heads), qkv)[1](g)[0]


def _spans_devices(axis_context) -> bool:
    """Whether the program being lowered is one a Mosaic kernel is refused
    in (``jax._src.tpu_custom_call``'s own test): GSPMD's over more than one
    device, or a ``shard_map`` that leaves some mesh axes automatic."""
    if isinstance(axis_context, sharding_impls.SPMDAxisContext):
        manual = axis_context.manual_axes | set(axis_context.mesh.manual_axes)
        return bool(axis_context.manual_axes) and manual != frozenset(
            axis_context.mesh.axis_names)
    return getattr(axis_context, "num_devices", 1) != 1


def _chosen_at_lowering(name, kernel, lax, out_lanes):
    """A primitive ``(*arrays, heads=)`` that lowers to ``kernel`` or, in a
    program that spans devices, to ``lax``; its result has the first
    array's shape with ``out_lanes(lanes)`` lanes."""
    primitive = Primitive(name)
    primitive.def_abstract_eval(lambda qkv, *_, heads: qkv.update(
        shape=qkv.shape[:2] + (out_lanes(qkv.shape[2]),)))

    def lower(ctx, *arrays, heads):
        spans = _spans_devices(ctx.module_context.axis_context)
        return mlir.lower_fun(
            functools.partial(lax if spans else kernel, heads=heads),
            multiple_results=False)(ctx, *arrays)

    def batch(arrays, dims, heads):
        """``vmap``: the mapped axis folds into the images."""
        size = next(a.shape[d] for a, d in zip(arrays, dims) if d is not None)
        fold = [(jnp.moveaxis(a, d, 0) if d is not None else
                 jnp.broadcast_to(a, (size,) + a.shape)).reshape(
                     (-1,) + a.shape[-2:]) for a, d in zip(arrays, dims)]
        out = primitive.bind(*fold, heads=heads)
        return out.reshape((size, -1) + out.shape[1:]), 0

    mlir.register_lowering(primitive, lower)
    batching.primitive_batchers[primitive] = batch
    return primitive


_forward_p = _chosen_at_lowering("global_attention_forward", _forward,
                                 _lax_forward, lambda lanes: lanes // 3)
_backward_p = _chosen_at_lowering("global_attention_backward", _backward,
                                  _lax_backward, lambda lanes: lanes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _attend(qkv, heads):
    return _forward_p.bind(qkv, heads=heads)


def _attend_fwd(qkv, heads):
    return _forward_p.bind(qkv, heads=heads), qkv


def _attend_bwd(heads, qkv, g):
    return (_backward_p.bind(qkv, g, heads=heads),)


_attend.defvjp(_attend_fwd, _attend_bwd)


@functools.partial(jax.jit, static_argnames="heads")
def global_attention(qkv: jax.Array, *, heads: int) -> jax.Array:
    """Fused softmax attention of every token over every token of its image,
    differentiable in ``qkv``.

    qkv: (B, N, 3*C), the lanes ordered (q | k | v) x heads x d as
         ``nn.Dense(3*C)`` writes them.
    Returns (B, N, C), the heads side by side as ``proj`` reads them.
    """
    return _attend(qkv, heads)
