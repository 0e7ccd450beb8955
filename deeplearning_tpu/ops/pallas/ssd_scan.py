"""Mamba-2's chunked scan as Pallas kernels: a chunk's decays and products
never leave VMEM.

``ops/ssd.py::ssd_scan`` (the lax path, the CPU path and the oracle) writes,
for every chunk and head, the within-chunk decays ``exp(cum_l - cum_m)`` to
HBM as float32 (Q, Q) arrays, their product with ``C Bᵀ`` as bfloat16, and
autodiff's backward pass writes and reads several such arrays more. Here a
forward kernel and a backward kernel each take one chunk of ``lanes`` lanes
of heads a program (grid ``(B, Z chunks, H P / lanes)``, the chunk axis
before the heads, all ``arbitrary``), form a head's masked decays in VMEM,
and carry every head's (P, S) float32 state from chunk to chunk in VMEM
scratch: forward from the first chunk, backward its gradient from the last.

Per chunk and head (``c`` the chunk's cumulative sums of ``Δ A``, float32,
made outside the kernels by ``jnp.cumsum`` under the same scope; ``s`` the
state the chunk starts from):

    xdt = bf16(x Δ)
    y   = bf16((L ∘ C Bᵀ)) xdt + (C sᵀ) ∘ exp(c),   L = exp(c_l − c_m), m ≤ l
    s  ← s exp(c_Q) + bf16(xdt ∘ exp(c_Q − c))ᵀ B

with the lax path's precisions: decays, segment sums and states float32;
bfloat16 operands and float32 accumulation where the lax path has them;
``C sᵀ`` on the state split into a bfloat16 high and low part (two passes,
finer than the one bfloat16 pass a float32 product takes at the default
precision); ``y`` in x's dtype. The backward kernel's products of two float32
operands take three such passes. ``C Bᵀ`` is made once a group and chunk
(the first program of the group), its gradient summed over the group's heads
in VMEM. The forward keeps each chunk's starting states, (B, Z, H P, S)
float32, as the backward's residual.

Layout: x and y as the mixer holds them, (B, N, H P), B and C as (B, N, G S),
Δ and ``c`` (B, N, H) and ``c`` again as (B, H, N): a head's column and row
of ``c`` are picked by comparison (no lane-dynamic slice). The backward
kernel returns ``dx``, ``dB``, ``dC``, the part of ``dΔ`` that comes through
``x Δ`` and ``d c``; a reverse cumulative sum within chunks outside it gives
the rest of ``dΔ`` and ``dA``. The two calls are jitted: a model's Mamba
layers of one shape then trace and lower each kernel once (unjitted, nine
layers' 27 calls added 13 s to a step's lowering on a v5e host: PERF.md §6,
PR 39).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import interpret_mode

# lanes of heads a program takes: whole 128-lane tiles of one group, up to
# this many (fewer programs, fewer fixed costs; a head's work is the same)
_MAX_LANES = 512
_TILE = 128
_VMEM_LIMIT = 64 * 2 ** 20
_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))       # a bᵀ
_TN = (((0,), (0,)), ((), ()))       # aᵀ b
_NN = (((1,), (0,)), ((), ()))       # a b


def program_lanes(heads: int, head_width: int, groups: int) -> int | None:
    """Lanes of heads a program takes: the most whole 128-lane tiles, up to
    ``_MAX_LANES``, that divide a group's heads; None if a head does not
    divide a tile or a group's heads fill no whole tile."""
    if _TILE % head_width or heads % groups:
        return None
    group_lanes = heads // groups * head_width
    for lanes in range(_MAX_LANES, 0, -_TILE):
        if group_lanes % lanes == 0:
            return lanes
    return None


def select_path(tokens: int, heads: int, head_width: int, state: int,
                groups: int, chunk: int, dtype, *,
                initializing: bool = False) -> str:
    """``"fused"`` where the kernels compile (not the CPU backend, where they
    would run interpreted) and the shapes fit them: bfloat16 x, a chunk of
    whole 128-row tiles, heads of a width that divides a 128-lane tile
    filling whole tiles of a group, a state of whole 128-lane tiles and a
    whole number of chunks; ``"lax"`` everywhere else and while
    ``model.init`` runs the layer once, eagerly."""
    covered = (jnp.dtype(dtype) == jnp.bfloat16 and chunk % 128 == 0
               and state % 128 == 0 and tokens % chunk == 0
               and program_lanes(heads, head_width, groups) is not None)
    return ("fused" if covered and not (initializing or interpret_mode())
            else "lax")


def _mm(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _split(a):
    """A float32 array as a bfloat16 high part and the bfloat16 of what it
    leaves."""
    hi = a.astype(jnp.bfloat16)
    return hi, (a - hi.astype(_F32)).astype(jnp.bfloat16)


def _mm_exact_f32(a, b, dims=_NN):
    """``a`` bfloat16 (exact), ``b`` float32 in two bfloat16 passes."""
    hi, lo = _split(b)
    return _mm(a, hi, dims) + _mm(a, lo, dims)


def _mm_f32_exact(a, b, dims=_NN):
    """``a`` float32 in two bfloat16 passes, ``b`` bfloat16 (exact)."""
    hi, lo = _split(a)
    return _mm(hi, b, dims) + _mm(lo, b, dims)


def _mm_f32(a, b, dims=_NN):
    """Two float32 operands in three bfloat16 passes."""
    (ah, al), (bh, bl) = _split(a), _split(b)
    return _mm(ah, bh, dims) + _mm(al, bh, dims) + _mm(ah, bl, dims)


def _column(block, head):
    """Column ``head`` of a (Q, H) block as (Q, 1), exact."""
    lane = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(lane == head, block, 0.0), axis=1, keepdims=True)


def _row(block, head):
    """Row ``head`` of an (H, Q) block as (1, Q), exact."""
    row = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0)
    return jnp.sum(jnp.where(row == head, block, 0.0), axis=0, keepdims=True)


class _Heads:
    """The program's heads: ``lanes // p`` heads of ``p`` lanes from head
    ``first``; each head's ``c`` and ``Δ`` as (Q, 1) columns and spread over
    its lanes as (Q, lanes)."""

    def __init__(self, first, p, lanes, cum_ref, cumt_ref, dt_ref):
        self.first, self.p, self.count = first, p, lanes // p
        cum, dt = cum_ref[0], dt_ref[0]
        self.cumt = cumt_ref[0]
        self.cols = [_column(cum, first + i) for i in range(self.count)]
        self.dts = [_column(dt, first + i) for i in range(self.count)]
        q = cum.shape[0]
        self.head_of_lane = jax.lax.broadcasted_iota(
            jnp.int32, (q, lanes), 1) // p
        self.cum = self.spread(self.cols)
        self.dt = self.spread(self.dts)

    def spread(self, columns):
        """(Q, 1) a head -> (Q, lanes), each head's over its lanes."""
        out = columns[0]
        for i in range(1, self.count):
            out = jnp.where(self.head_of_lane == i, columns[i], out)
        return jnp.broadcast_to(out, self.head_of_lane.shape)

    def decays(self, i, causal):
        """Head ``i``'s (Q, Q) within-chunk decays ``exp(c_l - c_m)``, zero
        above the diagonal, float32."""
        diff = self.cols[i] - _row(self.cumt, self.first + i)
        return jnp.exp(jnp.where(causal, diff, -jnp.inf))

    def tile(self, i):
        """Head ``i``'s 128-lane tile and its lanes there."""
        t = i * self.p // _TILE
        lane = jax.lax.broadcasted_iota(
            jnp.int32, (self.head_of_lane.shape[0], _TILE), 1)
        return slice(t * _TILE, (t + 1) * _TILE), \
            (lane + t * _TILE) // self.p == i

    def lane_sum(self, i, a):
        """Sum over head ``i``'s lanes of (Q, lanes) ``a``: (Q, 1)."""
        return jnp.sum(jnp.where(self.head_of_lane == i, a, 0.0), axis=1,
                       keepdims=True)

    def row_decay(self, rows):
        """(lanes, 1): ``exp(c_Q)`` of each state row's head."""
        head = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // self.p
        out = jnp.exp(self.cols[0][-1:, :])
        for i in range(1, self.count):
            out = jnp.where(head == i, jnp.exp(self.cols[i][-1:, :]), out)
        return jnp.broadcast_to(out, (rows, 1))


def _causal(q):
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return col <= row


def _fwd_kernel(x_ref, dt_ref, cum_ref, cumt_ref, b_ref, c_ref, *refs,
                p: int, per_group: int, keep: bool):
    # x_ref: (1, Q, lanes); dt_ref, cum_ref: (1, Q, H) float32; cumt_ref:
    # (1, H, Q) float32; b_ref, c_ref: (1, Q, S); y_ref like x_ref;
    # start_ref: (1, 1, lanes, S) float32 (with ``keep``); state: (programs
    # a row of heads, lanes, S) float32; cb: (Q, Q) float32
    if keep:
        y_ref, start_ref, state, cb = refs
    else:
        (y_ref, state, cb), start_ref = refs, None
    z, j = pl.program_id(1), pl.program_id(2)
    q, lanes = x_ref.shape[1:]

    @pl.when(z == 0)
    def _():
        state[j] = jnp.zeros(state.shape[1:], _F32)

    @pl.when(j % per_group == 0)
    def _():
        cb[...] = _mm(c_ref[0], b_ref[0], _NT)

    s0 = state[j]
    if keep:
        start_ref[0, 0] = s0
    heads = _Heads(j * (lanes // p), p, lanes, cum_ref, cumt_ref, dt_ref)
    cmat, bmat = c_ref[0], b_ref[0]
    xdt = (x_ref[0].astype(_F32) * heads.dt).astype(x_ref.dtype)
    # the states' part: C sᵀ decayed to each position
    y = _mm_exact_f32(cmat, s0, _NT) * jnp.exp(heads.cum)
    # within the chunk, a head at a time on its 128-lane tile
    causal, cbv = _causal(q), cb[...]
    parts = [y[:, t * _TILE:(t + 1) * _TILE] for t in range(lanes // _TILE)]
    for i in range(heads.count):
        lanes_i, mine = heads.tile(i)
        m = (heads.decays(i, causal) * cbv).astype(x_ref.dtype)
        t = lanes_i.start // _TILE
        parts[t] = parts[t] + jnp.where(mine, _mm(m, xdt[:, lanes_i]), 0.0)
    y_ref[0] = jnp.concatenate(parts, axis=1).astype(y_ref.dtype)
    # the state the next chunk starts from
    last = heads.cum[-1:, :]
    w = (xdt.astype(_F32) * jnp.exp(last - heads.cum)).astype(x_ref.dtype)
    state[j] = s0 * heads.row_decay(lanes) + _mm(w, bmat, _TN)


def _bwd_kernel(x_ref, dt_ref, cum_ref, cumt_ref, b_ref, c_ref, start_ref,
                dy_ref, dx_ref, ddt_ref, dcum_ref, db_ref, dc_ref,
                ds, cb, dcb, db_acc, dc_acc, *, p: int, per_group: int):
    # the forward's operands at one chunk (the grid runs the chunks last to
    # first) and dy_ref like x_ref; dx_ref like x_ref; ddt_ref, dcum_ref:
    # (1, Q, H) float32, the program's heads' columns written, the block kept
    # over a row of programs; db_ref, dc_ref: (1, Q, S), written by a group's
    # last program; ds: (programs a row, lanes, S) float32, the gradient of
    # the state the next chunk starts from; cb, dcb: (Q, Q) float32; db_acc,
    # dc_acc: (Q, S) float32
    z, j = pl.program_id(1), pl.program_id(2)
    q, lanes = x_ref.shape[1:]

    @pl.when(z == 0)
    def _():
        ds[j] = jnp.zeros(ds.shape[1:], _F32)

    @pl.when(j % per_group == 0)
    def _():
        cb[...] = _mm(c_ref[0], b_ref[0], _NT)
        dcb[...] = jnp.zeros(dcb.shape, _F32)
        db_acc[...] = jnp.zeros(db_acc.shape, _F32)
        dc_acc[...] = jnp.zeros(dc_acc.shape, _F32)

    @pl.when(j == 0)
    def _():
        ddt_ref[...] = jnp.zeros(ddt_ref.shape, _F32)
        dcum_ref[...] = jnp.zeros(dcum_ref.shape, _F32)

    s0, dsn = start_ref[0, 0], ds[j]
    heads = _Heads(j * (lanes // p), p, lanes, cum_ref, cumt_ref, dt_ref)
    cmat, bmat = c_ref[0], b_ref[0]
    x = x_ref[0].astype(_F32)
    xdt = (x * heads.dt).astype(x_ref.dtype)
    last = heads.cum[-1:, :]
    to_end = jnp.exp(last - heads.cum)
    w_f = xdt.astype(_F32) * to_end
    dy = dy_ref[0]
    # the states' part of y: y = (C sᵀ) ∘ exp(c)
    dye = dy.astype(_F32) * jnp.exp(heads.cum)
    cs = _mm_exact_f32(cmat, s0, _NT)
    dc_acc[...] += _mm_f32(dye, s0)
    decay = heads.row_decay(lanes)
    ds[j] = dsn * decay + _mm_f32_exact(dye, cmat, _TN)
    # the state the next chunk starts from: s0 exp(c_Q) + wᵀ B
    dw = _mm_exact_f32(bmat, dsn, _NT)
    db_acc[...] += _mm_exact_f32(w_f.astype(x_ref.dtype), dsn)
    dxdt = dw * to_end
    tq = dw * w_f                    # d c_Q, and minus d c_m, a lane each
    own = dye * cs - tq               # d c_l from the state's terms
    sds = dsn * s0
    causal, cbv = _causal(q), cb[...]
    row_head = jax.lax.broadcasted_iota(jnp.int32, (lanes, 1), 0) // p
    last_row = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    hsel = jax.lax.broadcasted_iota(jnp.int32, dcum_ref.shape[1:], 1)
    parts = [dxdt[:, t * _TILE:(t + 1) * _TILE]
             for t in range(lanes // _TILE)]
    dcum, ddt = dcum_ref[0], ddt_ref[0]
    dcb_sum = dcb[...]
    for i in range(heads.count):
        lanes_i, mine = heads.tile(i)
        t = lanes_i.start // _TILE
        decays = heads.decays(i, causal)
        m_f = decays * cbv
        d_m = _mm(dy[:, lanes_i], jnp.where(mine, xdt[:, lanes_i], 0), _NT)
        parts[t] = parts[t] + jnp.where(
            mine, _mm(m_f.astype(x_ref.dtype), dy[:, lanes_i], _TN), 0.0)
        e = d_m * m_f
        dcb_sum = dcb_sum + d_m * decays
        col = (jnp.sum(e, axis=1, keepdims=True)
               - jnp.sum(e.T, axis=1, keepdims=True)
               + heads.lane_sum(i, own))
        at_end = jnp.sum(jnp.where(heads.head_of_lane == i, tq, 0.0)) \
            + jnp.sum(jnp.where(row_head == i, sds, 0.0)) \
            * jnp.exp(heads.cols[i][-1:, :])
        col = col + jnp.where(last_row, at_end, 0.0)
        dcum = jnp.where(hsel == heads.first + i, col, dcum)
    dcb[...] = dcb_sum
    dxdt = jnp.concatenate(parts, axis=1)
    dx_ref[0] = (dxdt * heads.dt).astype(dx_ref.dtype)
    dxx = dxdt * x
    for i in range(heads.count):
        ddt = jnp.where(hsel == heads.first + i, heads.lane_sum(i, dxx), ddt)
    ddt_ref[0] = ddt
    dcum_ref[0] = dcum

    @pl.when(j % per_group == per_group - 1)
    def _():
        dc_ref[0] = (dc_acc[...] + _mm_f32_exact(dcb_sum, bmat)
                     ).astype(dc_ref.dtype)
        db_ref[0] = (db_acc[...] + _mm_f32_exact(dcb_sum, cmat, _TN)
                     ).astype(db_ref.dtype)


def _specs(n, h, lanes, s, chunk, per_group, reverse):
    """BlockSpecs of the forward's operands over the grid (B, Z, programs a
    row): x's lanes, Δ and c, c's rows, B, C."""
    zc = (lambda z: n // chunk - 1 - z) if reverse else (lambda z: z)
    return [
        pl.BlockSpec((1, chunk, lanes), lambda b, z, j: (b, zc(z), j)),
        pl.BlockSpec((1, chunk, h), lambda b, z, j: (b, zc(z), 0)),
        pl.BlockSpec((1, chunk, h), lambda b, z, j: (b, zc(z), 0)),
        pl.BlockSpec((1, h, chunk), lambda b, z, j: (b, 0, zc(z))),
        pl.BlockSpec((1, chunk, s), lambda b, z, j: (b, zc(z), j // per_group)),
        pl.BlockSpec((1, chunk, s), lambda b, z, j: (b, zc(z), j // per_group)),
    ]


def _geometry(x, dt, b, chunk, groups):
    """Batch, tokens, H P, heads, head width, state, a program's lanes,
    programs a row of heads and a group's, chunks."""
    bsz, n, hp = x.shape
    h = dt.shape[-1]
    lanes = program_lanes(h, hp // h, groups)
    return (bsz, n, hp, h, hp // h, b.shape[-1] // groups, lanes,
            hp // lanes, hp // lanes // groups, n // chunk)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _forward(x, dt, cum, b, c, chunk, groups, keep):
    bsz, n, hp, h, p, s, lanes, rows, per_group, zs = _geometry(
        x, dt, b, chunk, groups)
    out_specs = [pl.BlockSpec((1, chunk, lanes), lambda b_, z, j: (b_, z, j))]
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    if keep:
        out_specs.append(pl.BlockSpec((1, 1, lanes, s),
                                      lambda b_, z, j: (b_, z, j, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bsz, zs, hp, s), _F32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, p=p, per_group=per_group, keep=keep),
        grid=(bsz, zs, rows),
        in_specs=_specs(n, h, lanes, s, chunk, per_group, False),
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((rows, lanes, s), _F32),
                        pltpu.VMEM((chunk, chunk), _F32)],
        compiler_params=_params(),
        interpret=interpret_mode(),
        name="ssd_scan_fwd",
    )(x, dt, cum, jnp.swapaxes(cum, 1, 2), b, c)
    return out if keep else out[0]


@functools.partial(jax.jit, static_argnums=(7, 8))
def _backward(x, dt, cum, b, c, starts, dy, chunk, groups):
    bsz, n, hp, h, p, s, lanes, rows, per_group, zs = _geometry(
        x, dt, b, chunk, groups)
    zc = lambda z: zs - 1 - z
    return pl.pallas_call(
        functools.partial(_bwd_kernel, p=p, per_group=per_group),
        grid=(bsz, zs, rows),
        in_specs=_specs(n, h, lanes, s, chunk, per_group, True) + [
            pl.BlockSpec((1, 1, lanes, s), lambda b_, z, j: (b_, zc(z), j, 0)),
            pl.BlockSpec((1, chunk, lanes), lambda b_, z, j: (b_, zc(z), j)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, lanes), lambda b_, z, j: (b_, zc(z), j)),
            pl.BlockSpec((1, chunk, h), lambda b_, z, j: (b_, zc(z), 0)),
            pl.BlockSpec((1, chunk, h), lambda b_, z, j: (b_, zc(z), 0)),
            pl.BlockSpec((1, chunk, s),
                         lambda b_, z, j: (b_, zc(z), j // per_group)),
            pl.BlockSpec((1, chunk, s),
                         lambda b_, z, j: (b_, zc(z), j // per_group)),
        ],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(dt.shape, _F32),
                   jax.ShapeDtypeStruct(dt.shape, _F32),
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype)],
        scratch_shapes=[pltpu.VMEM((rows, lanes, s), _F32),
                        pltpu.VMEM((chunk, chunk), _F32),
                        pltpu.VMEM((chunk, chunk), _F32),
                        pltpu.VMEM((chunk, s), _F32),
                        pltpu.VMEM((chunk, s), _F32)],
        compiler_params=_params(),
        interpret=interpret_mode(),
        name="ssd_scan_bwd",
    )(x, dt, cum, jnp.swapaxes(cum, 1, 2), b, c, starts, dy)


def _cumulative(dt, a, chunk):
    """``c``: the cumulative sums of ``Δ A`` within each chunk, float32,
    (B, N, H)."""
    bsz, n, h = dt.shape
    return jnp.cumsum((dt * a).reshape(bsz, n // chunk, chunk, h),
                      axis=2).reshape(bsz, n, h)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(x, dt, a, b, c, chunk, groups):
    with jax.named_scope("ssd_scan"):
        return _forward(x, dt, _cumulative(dt, a, chunk), b, c, chunk,
                        groups, keep=False)


def _scan_fwd(x, dt, a, b, c, chunk, groups):
    with jax.named_scope("ssd_scan"):
        cum = _cumulative(dt, a, chunk)
        y, starts = _forward(x, dt, cum, b, c, chunk, groups, keep=True)
    return y, (x, dt, a, b, c, cum, starts)


def _scan_bwd(chunk, groups, res, dy):
    x, dt, a, b, c, cum, starts = res
    with jax.named_scope("ssd_scan"):
        dx, ddt, dcum, db, dc = _backward(x, dt, cum, b, c, starts, dy,
                                          chunk, groups)
        bsz, n, h = dt.shape
        # c_l = sum of Δ A over the chunk's positions 0 .. l
        dda = jax.lax.cumsum(dcum.reshape(bsz, n // chunk, chunk, h), axis=2,
                             reverse=True).reshape(bsz, n, h)
        return (dx, ddt + dda * a, jnp.sum(dda * dt, axis=(0, 1)), db, dc)


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, chunk: int) -> jax.Array:
    """``ops/ssd.py::ssd_scan`` by the kernels, for shapes ``select_path``
    takes: x (B, N, H, P) bfloat16, dt (B, N, H), a (H,), b, c (B, N, G, S)
    -> y (B, N, H, P) in x's dtype."""
    bsz, n, h, p = x.shape
    g, s = b.shape[-2:]
    with jax.named_scope("ssd_scan"):
        y = _scan(x.reshape(bsz, n, h * p), dt.astype(_F32), a.astype(_F32),
                  b.reshape(bsz, n, g * s), c.reshape(bsz, n, g * s), chunk, g)
    return y.reshape(bsz, n, h, p)
