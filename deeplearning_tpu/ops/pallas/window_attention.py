"""Fused v1 window attention, forward and backward — Swin's score path
kept in VMEM.

The reference hand-fuses roll+partition in CUDA (classification/
swin_transformer/kernels/window_process/swin_window_process_kernel.cu:41-64)
because torch dispatches each of roll/view/permute as a separate kernel. On
the TPU the cost sits elsewhere: the lax path writes the float32 scores
``(B*nW, heads, N, N)`` to HBM four or five times forward and more backward,
at a 49-wide minor dimension that the (8, 128) tiling pads threefold. Here
``QK^T*scale + relative bias + shift mask -> softmax -> PV`` runs per block
of windows in VMEM, and so does its backward: one ``jax.custom_vjp`` whose
backward kernel recomputes the softmax from q, k and the combined bias and
emits ``dqkv`` and the bias gradient, summed over windows inside the kernel
(a resident accumulator over the grid's second axis, one partial sum per
mask-row block). No array with an ``N x N`` trailing shape and a ``B*nW``
leading one reaches HBM; what does is the combined bias + mask, at most
``max(nW, windows a program)`` windows of it, and the bias gradient's
partial sums.

Layout: XLA keeps Swin's activations token-major on the chip (the window
partition's copy and the qkv matmul both write ``(N, BW, lanes)`` physically),
so that is what the kernels read and write: ``qkv`` and ``do`` as
``(N, BW, 3*C)`` / ``(N, BW, C)``, ``o`` and ``dqkv`` the same, blocks
``(N, wb, lanes)`` of ``wb`` windows. ``window_attention`` takes and returns
the ``(BW, N, lanes)`` rows its callers have; the transposes inside it are
logical, XLA makes them bitcasts, and no layout copy stands at either side of
either kernel (PR 31; a row-major ``pallas_call`` operand had one on every
operand and result, 2.1 GB a Swin-T step at batch 128). The
``(token, window) -> (window, slot, lanes)`` arrangement the pair mathematics
wants is made in VMEM on the way in and undone on the way out; heads are lane
slices inside the kernel. A window holds ``slot = 64`` rows there: the rows
past ``N`` are zeros the kernel makes (the windows past ``BW`` in a ragged
last block are zeroed); as keys they carry -1e9 in the combined bias and
vanish in the softmax, as queries their output rows are never written.
Numbers as the lax path has them: ``q*scale`` in the input dtype, scores,
bias, mask and softmax in float32, ``p`` cast to the input dtype for ``PV``,
float32 accumulation.

``select_path`` is the one place that chooses between this and
``ops/window_utils.windowed_attention_reference`` (the oracle).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import interpret_mode

# (pairs of heads) x windows one program unrolls: its code size, its compile
# time and, with C, the VMEM its blocks and live rows take. The windows are
# the second-minor axis of every block, so whole tiles of 8 sublanes or all of
# them: 16 up to 12 heads, 8 beyond (Swin-T at batch 128 on a v5e, PR 31: at
# 3 heads 16 is a fifth faster than 8 and as fast as 32, at 12 heads a tenth
# faster than 8; at 24 heads it saves 0.02 ms a block and compiles three
# times as long)
_UNITS_PER_PROGRAM = 96
_SUBLANES = 8
_MASKED = -1e9
_VMEM_LIMIT = 64 * 2 ** 20


def select_path(v2: bool, initializing: bool = False) -> str:
    """Which window attention a layer runs, from what the code can see:
    ``"fused"`` for v1 (bias-table) attention wherever the kernels compile,
    ``"lax"`` (``windowed_attention_reference`` / the cosine path) for v2, on
    a CPU backend, where the kernels would run interpreted, and while
    ``model.init`` runs the layer once, eagerly, at batch 1: a kernel traced,
    lowered and loaded for that one call costs set-up seconds and nothing is
    trained or served by it."""
    return "lax" if v2 or initializing or interpret_mode() else "fused"


def interface(path: str) -> Optional[str]:
    """The order of the rows a ``path`` of ``select_path`` hands its kernels:
    ``"token_major"``, ``(N, BW, lanes)``, for the fused one (the order XLA
    keeps them in on the chip, so no copy stands at the kernels' boundary);
    None for the lax path, which runs no kernel."""
    return "token_major" if path == "fused" else None


def _slot(n: int) -> int:
    """Rows (and key lanes) a window's ``n`` tokens take in VMEM: 64, so that
    two heads' scores fill a 128-lane tile; larger windows a multiple of 16."""
    return max(64, -(-n // 16) * 16)


def windows_per_program(bw: int, nw: int, heads: int) -> int:
    """Windows a program takes, the blocks' second axis: 16 or, with many
    heads, 8 (whole sublane tiles), and either a divisor of ``nw`` (a program
    stays inside one image's mask rows) or a multiple of it (whole images a
    program); 8 whole images where ``nw`` pairs with neither; all ``bw``
    windows where that is no more than one program's."""
    pairs = -(-heads // 2)
    wanted = 2 * _SUBLANES if 2 * _SUBLANES * pairs <= _UNITS_PER_PROGRAM \
        else _SUBLANES
    fits = [w for w in (wanted, _SUBLANES) if nw % w == 0 or w % nw == 0]
    wb = fits[0] if fits else math.lcm(nw, _SUBLANES)
    return wb if wb < bw else bw


def _zero_outside(x, n: int, windows_left):
    """Zero the rows past ``n`` and the windows past ``windows_left`` of a
    ``(wb, slot, lanes)`` block: both lie outside the array."""
    ok = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) < n
    if windows_left is not None:
        ok &= jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) < windows_left
    return jnp.where(ok, x, jnp.zeros_like(x))


def _windows_left(bw: int, wb: int, ragged: bool):
    if not ragged:
        return None
    first = (pl.program_id(1) * pl.num_programs(0) + pl.program_id(0)) * wb
    return bw - first


def _stack_heads(x, d: int):
    """Two heads' rows ``(wb, slot, 2d)`` as the block-diagonal
    ``(wb, 2*slot, 2d)``: the first head's rows keep its ``d`` lanes, the
    second's rows its own, so one contraction serves both heads and their
    cross terms are exact zeros. One head ``(wb, slot, d)``: zero rows stand
    in for the partner."""
    zero = jnp.zeros_like(x)
    if x.shape[-1] == d:
        return jnp.concatenate([x, zero], axis=1)
    first = jax.lax.broadcasted_iota(jnp.int32, x.shape, 2) < d
    return jnp.concatenate([jnp.where(first, x, zero),
                            jnp.where(first, zero, x)], axis=1)


def _unstack_heads(x, d: int):
    """Each head's own block of a ``(wb, 2*slot, lanes)`` product with a
    ``_stack_heads`` operand, back as ``(wb, slot, lanes)``."""
    slot = x.shape[1] // 2
    if x.shape[-1] == d:
        return x[:, :slot]
    first = jax.lax.broadcasted_iota(jnp.int32, (1, slot, x.shape[-1]), 2) < d
    return jnp.where(first, x[:, :slot], x[:, slot:])


def _softmax_over_keys(s):
    """Softmax down the rows (keys) of ``(wb, keys, queries)`` float32
    scores: the reductions run across vregs and sublanes, not lanes."""
    e = jnp.exp(s - jnp.max(s, axis=1, keepdims=True))
    # the reciprocal on the column sums, not a division of the whole tile
    return e * (1.0 / jnp.sum(e, axis=1, keepdims=True))


def _pair_lanes(heads: int, d: int):
    """(first lane, width) of each pair of heads inside q's, k's or v's
    ``C`` lanes; an odd last head stands alone."""
    return [(h * d, d * min(2, heads - h)) for h in range(0, heads, 2)]


_NT = (((2,), (2,)), ((0,), (0,)))      # contract lanes with lanes
_NN = (((2,), (1,)), ((0,), (0,)))      # lanes with the other's rows
_TN = (((1,), (1,)), ((0,), (0,)))      # rows with rows


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


# Inside the kernels the scores are held transposed and two heads wide:
# ``(wb, slot keys, 2*slot queries)``, head A's queries in the first ``slot``
# lanes and head B's in the rest. Keys down the rows make the softmax's
# reductions cheap; two heads across fill the 128 lanes that one head's 49
# queries leave mostly empty, and halve the number of MXU passes. The work of
# one pair of heads is a jitted function, so that a kernel's trace holds it
# once however many heads there are (lowering inlines it; the step's set-up
# time is the reason).

@functools.partial(jax.jit, static_argnames="d")
def _pair_forward(q, k, v, bias, d):
    q = _stack_heads(q * (d ** -0.5), d)
    p = _softmax_over_keys(_dot(k, q, _NT) + bias)
    return _unstack_heads(_dot(p.astype(v.dtype), v, _TN), d)


@functools.partial(jax.jit, static_argnames="d")
def _pair_backward(q, k, v, do, bias, d):
    """(dq, dk, dv, the bias gradient summed over the block's windows)."""
    scale = d ** -0.5
    q = _stack_heads(q * scale, d)
    do = _stack_heads(do, d)
    p = _softmax_over_keys(_dot(k, q, _NT) + bias)
    dv = _dot(p.astype(do.dtype), do, _NN)
    dp = _dot(v, do, _NT)
    ds = p * (dp - jnp.sum(p * dp, axis=1, keepdims=True))
    dbias = jnp.sum(ds, axis=0)
    ds = ds.astype(k.dtype)
    dq = _unstack_heads(_dot(ds, k, _TN), d) * scale
    return dq, _dot(ds, q, _NN), dv, dbias


# The swap of a block's two leading axes runs on float32 rows, whatever the
# input dtype: on a v5e (PR 31) Mosaic's swap of packed bf16 sublanes gave
# wrong ``do`` rows at Swin-T's third stage (384 lanes; 11 % off in the block's
# input gradient, every other stage exact), the 32-bit one is exact at all
# four against the row-major kernels and costs 0.13 ms forward, 0.31 ms
# backward of a 13.6 ms first-stage block.

def _windows_major(ref, slot: int, windows_left):
    """A token-major ``(n, wb, lanes)`` block as the ``(wb, slot, lanes)``
    rows the pair mathematics reads: zero rows appended past ``n`` (whole
    tiles of the leading axis, no data moves), then the two leading axes
    swapped in VMEM; the windows past ``windows_left`` zeroed."""
    x = ref[...]
    n, wb, lanes = x.shape
    rows = jnp.concatenate([x.astype(jnp.float32),
                            jnp.zeros((slot - n, wb, lanes), jnp.float32)])
    rows = jnp.swapaxes(rows, 0, 1).astype(x.dtype)
    if windows_left is None:
        return rows
    return _zero_outside(rows, n, windows_left)


def _store_token_major(ref, rows_ref):
    """``(wb, slot, lanes)`` rows into a token-major ``(n, wb, lanes)`` block,
    the rows past ``n`` dropped."""
    rows = jnp.swapaxes(rows_ref[...].astype(jnp.float32), 0, 1)
    ref[...] = rows[:ref.shape[0]].astype(ref.dtype)


def _fwd_kernel(qkv_ref, bias_ref, o_ref, o_rows, *, heads, bw, ragged):
    _, wb, c3 = qkv_ref.shape
    c = c3 // 3
    d = c // heads
    qkv = _windows_major(qkv_ref, o_rows.shape[1],
                         _windows_left(bw, wb, ragged))
    for j, (lo, w) in enumerate(_pair_lanes(heads, d)):
        q, k, v = (qkv[:, :, at + lo:at + lo + w] for at in (0, c, 2 * c))
        o = _pair_forward(q, k, v, bias_ref[:, j], d=d)
        o_rows[:, :, lo:lo + w] = o.astype(o_rows.dtype)
    _store_token_major(o_ref, o_rows)


def _bwd_kernel(qkv_ref, bias_ref, do_ref, dqkv_ref, dbias_ref, dqkv_rows, *,
                heads, bw, ragged):
    _, wb, c3 = qkv_ref.shape
    c = c3 // 3
    d = c // heads
    slot = dqkv_rows.shape[1]
    left = _windows_left(bw, wb, ragged)
    qkv = _windows_major(qkv_ref, slot, left)
    do = _windows_major(do_ref, slot, left)

    @pl.when(pl.program_id(1) == 0)
    def _():
        dbias_ref[...] = jnp.zeros_like(dbias_ref)

    for j, (lo, w) in enumerate(_pair_lanes(heads, d)):
        q, k, v = (qkv[:, :, at + lo:at + lo + w] for at in (0, c, 2 * c))
        *dqkv, dbias = _pair_backward(q, k, v, do[:, :, lo:lo + w],
                                      bias_ref[:, j], d=d)
        dbias_ref[0, j] += dbias
        for at, grad in zip((0, c, 2 * c), dqkv):
            dqkv_rows[:, :, at + lo:at + lo + w] = grad.astype(
                dqkv_rows.dtype)
    _store_token_major(dqkv_ref, dqkv_rows)


def _pack_bias(comb, slot: int):
    """``(windows, heads, N queries, N keys)`` additive terms as the kernels
    read them: ``(windows, pairs, slot keys, 2*slot queries)``, a pair of
    heads side by side in the lanes. Padded key rows carry -1e9; padded
    query columns and an odd head's absent partner 0 (their columns of the
    scores are never read)."""
    windows, heads, n, _ = comb.shape
    pairs = -(-heads // 2)
    comb = jnp.pad(comb, ((0, 0), (0, 0), (0, 0), (0, slot - n)),
                   constant_values=_MASKED)
    comb = jnp.pad(comb, ((0, 0), (0, 2 * pairs - heads), (0, slot - n),
                          (0, 0)))
    comb = comb.reshape(windows, pairs, 2, slot, slot)
    # (windows, pairs, head, query, key) -> (windows, pairs, key, head, query)
    return comb.transpose(0, 1, 4, 2, 3).reshape(windows, pairs, slot,
                                                 2 * slot)


def _unpack_bias(packed, heads: int, n: int):
    """``_pack_bias``'s layout back to ``(windows, heads, N, N)``."""
    windows, pairs, slot, _ = packed.shape
    x = packed.reshape(windows, pairs, slot, 2, slot).transpose(0, 1, 3, 4, 2)
    return x.reshape(windows, 2 * pairs, slot, slot)[:, :heads, :n, :n]


def _combined_bias(bias, mask, slot: int, windows: int):
    """bias + shift mask as one additive float32 term, packed for the
    kernels (``windows`` 1 without a mask, else a multiple of ``nW``,
    tiled)."""
    comb = bias[None].astype(jnp.float32)
    if mask is not None:
        comb = comb + mask[:, None].astype(jnp.float32)
        comb = jnp.tile(comb, (windows // mask.shape[0], 1, 1, 1))
    return _pack_bias(comb, slot)


def _plan(qkv, mask, heads: int):
    """Block sizes from the shapes of the token-major ``(N, BW, 3*C)`` rows:
    windows a program, the grid (mask-row block, image block: the second runs
    fastest, so a mask block is fetched once), the combined bias's window
    count, and whether the last block is ragged."""
    _, bw, _ = qkv.shape
    nw = 1 if mask is None else mask.shape[0]
    wb = windows_per_program(bw, nw, heads)
    tile = max(nw, wb)                      # windows of one pass over j
    grid = (tile // wb, pl.cdiv(bw, tile))
    return wb, grid, (1 if mask is None else tile), bool(bw % wb)


def _specs(wb, n, c, grid, comb):
    """Blocks of the qkv rows, of the packed bias (one shared block without
    a mask) and of the output rows: ``wb`` windows of the second axis."""
    nj = grid[0]
    windows, pairs, slot, lanes = comb.shape
    shared = windows == 1
    rows = lambda width: pl.BlockSpec(           # noqa: E731
        (n, wb, width), lambda j, b: (0, b * nj + j, 0))
    bias = pl.BlockSpec(
        (1 if shared else wb, pairs, slot, lanes),
        (lambda j, b: (0, 0, 0, 0)) if shared
        else (lambda j, b: (j, 0, 0, 0)))
    return rows(3 * c), bias, rows(c)


# jitted (as ``window_attention`` is) so that a stage's blocks share one trace
# and one lowering of their kernel: the step's set-up time again
@functools.partial(jax.jit, static_argnames=("heads", "plan"))
def _forward(qkv, comb, heads, plan):
    wb, grid, _, ragged = plan
    n, bw, c3 = qkv.shape
    c = c3 // 3
    qkv_spec, bias_spec, out_spec = _specs(wb, n, c, grid, comb)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, bw=bw, ragged=ragged),
        grid=grid,
        in_specs=[qkv_spec, bias_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((n, bw, c), qkv.dtype),
        scratch_shapes=[pltpu.VMEM((wb, comb.shape[2], c), qkv.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret_mode(),
        name="window_attention_fwd",
    )(qkv, comb)


@functools.partial(jax.jit, static_argnames=("heads", "plan"))
def _backward(qkv, comb, g, heads, plan):
    wb, grid, _, ragged = plan
    n, bw, c3 = qkv.shape
    qkv_spec, bias_spec, out_spec = _specs(wb, n, c3 // 3, grid, comb)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, bw=bw, ragged=ragged),
        grid=grid,
        in_specs=[qkv_spec, bias_spec, out_spec],
        out_specs=[qkv_spec,
                   pl.BlockSpec((1,) + comb.shape[1:],
                                lambda j, b: (j, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(qkv.shape, qkv.dtype),
                   jax.ShapeDtypeStruct((grid[0],) + comb.shape[1:],
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((wb, comb.shape[2], c3), qkv.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret_mode(),
        name="window_attention_bwd",
    )(qkv, comb, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attend(qkv, bias, mask, heads):
    """Token-major in, token-major out: ``(N, BW, 3*C) -> (N, BW, C)``."""
    return _attend_fwd(qkv, bias, mask, heads)[0]


def _attend_fwd(qkv, bias, mask, heads):
    plan = _plan(qkv, mask, heads)
    comb = _combined_bias(bias, mask, _slot(qkv.shape[0]), plan[2])
    return _forward(qkv, comb, heads, plan), (qkv, comb, bias, mask)


def _attend_bwd(heads, residuals, g):
    qkv, comb, bias, mask = residuals
    n = qkv.shape[0]
    dqkv, dbias = _backward(qkv, comb, g, heads, _plan(qkv, mask, heads))
    dbias = jnp.sum(_unpack_bias(dbias, heads, n), axis=0).astype(bias.dtype)
    return dqkv, dbias, None if mask is None else jnp.zeros_like(mask)


_attend.defvjp(_attend_fwd, _attend_bwd)


@functools.partial(jax.jit, static_argnames="heads")
def window_attention(qkv: jax.Array, bias: jax.Array,
                     mask: Optional[jax.Array] = None, *,
                     heads: int) -> jax.Array:
    """Fused attention over partitioned windows, differentiable in ``qkv``
    and ``bias``.

    qkv:  (BW, N, 3*C) — BW = batch*num_windows, N = window², the lanes
          ordered (q | k | v) x heads x d as ``nn.Dense(3*C)`` writes them.
    bias: (heads, N, N) relative-position bias (trainable).
    mask: (nW, N, N) additive shift mask or None; window ``i`` takes row
          ``i % nW``.
    Returns (BW, N, C).

    The kernels work on the token-major ``(N, BW, lanes)`` arrays; the two
    transposes here (and their transposes going back) change no bytes where
    XLA is free to lay the rows out token-major, as it does on the chip.
    """
    out = _attend(jnp.swapaxes(qkv, 0, 1), bias, mask, heads)
    return jnp.swapaxes(out, 0, 1)
