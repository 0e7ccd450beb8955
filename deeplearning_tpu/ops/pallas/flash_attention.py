"""Flash attention for TPU: fused online-softmax attention in Pallas.

Per-head kernels that never write the (N, N) scores to HBM: forward and
backward are Pallas kernels with a custom VJP. The backward is one
pallas_call a head and key block: it makes each visible (query block, key
block) pair's scores, P = exp(S - LSE) from the saved logsumexp, dP and dS
once, and from them dV and dK and the head's dQ, which it sums in VMEM
across the key blocks (FlashAttention-2's backward, dQ kept on the chip).

Two callers. A causal decoder's attention core (``causal_attention``, path
by ``select_path``, blocks of 512, the key blocks past a query block's last
row skipped: GLM-4.7-Flash's latent attention at 4,096 tokens of head width
256, the benchmark's ``glm47_flash_ep8`` cell, PR 32; and Mellum2's grouped
heads at 8,192 tokens of width 128, 8 query heads reading one key/value
head, in sliding layers the key blocks before a query block's window
skipped too, the ``mellum2_ep4`` cell; and Granite 4.0-H's heads
of width 64, half a lane tile, 4 query heads reading one key/value head,
the ``granite4_h_micro_vocab8`` cell). And what
sequence parallelism calls, which stays until ROADMAP W8's long-sequence
row decides it on the chip: ``flash_attention_with_lse`` and
``flash_chunk_grads`` are the ring's ``use_flash`` chunks
(parallel/ring_attention.py: the per-row logsumexp lets the ring's
online-softmax merge combine per-chunk kernel outputs exactly),
``flash_attention`` is Ulysses' ``use_flash`` inner attention. Short
sequences (ViT's 197 tokens) are ``global_attention.select_path``'s,
windows are ``window_attention``'s. The head-batched variant that was meant for short
N lost to the lax path on the chip every time it was measured and was
deleted at PR 30 (PERF.md §6).

Layout: (B, H, N, D); K and V may have fewer heads, (B, H / g, N, D): query
head ``j`` then reads key/value head ``j // g`` through the block index, no
repeated K or V stands in HBM, and ``dK``, ``dV`` come out of the kernel a
query head each and are summed over a group after it (PERF.md §6, PR 34, for
the in-kernel sum that lost). N must be a multiple of the block size —
wrappers pad and mask via ``kv_len`` (the number of valid key tokens).
``window`` (causal only) lets query ``i`` see keys ``i - window + 1 .. i``.

What the backward pass keeps of the forward: ``(q, k, v, out, lse)``, the
logsumexp as ``(BH, N)`` float32 (the kernels read and write it 8 lanes wide,
lane 0 meaningful; the wide form is made round each call). ``out`` and
``lse`` carry names (``ATTENTION_OUT``, ``ATTENTION_LSE``) that a
``jax.checkpoint`` policy can save: a decoder block does (PR 35), so its
rematerialised backward pass does not run the forward kernel again.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import interpret_mode

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30
# Every program holds one head's whole K and V (the backward pass: Q, dO,
# logsumexp and delta, its dQ block and the float32 dQ sum) in VMEM: the
# forward 16.5 MB at 4,096 tokens of width 256, the backward some 31 MB there
# and 38 MB at 8,192 tokens of width 128 or 64 (``_bwd_calls``), over the
# compiler's 16 MB default on a v5e (128 MB there).
_VMEM_LIMIT = 96 * 2 ** 20
# ``_flash_fwd``'s names for its output and logsumexp (module docstring);
# outside a ``jax.checkpoint`` whose policy saves them they do nothing.
ATTENTION_OUT = "attention_out"
ATTENTION_LSE = "attention_lse"


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _causal_stop(qi, q_block: int, block_k: int, nk: int):
    """Key blocks a causal query block has to visit: those that start at or
    before its last row. The blocks after them are masked whole, so the loop
    ends there (half the work of a square at long sequences)."""
    return jnp.minimum(nk, ((qi + 1) * q_block + block_k - 1) // block_k)


def _key_loop(qi, q_block: int, block_k: int, nk: int, causal: bool, window):
    """(first, one past the last) key block a query block visits: causal,
    up to ``_causal_stop``; with a window, from the block that holds key
    ``first row - window + 1``."""
    if not causal:
        return 0, nk
    first = 0 if window is None else jnp.maximum(
        0, (qi * q_block - window + 1) // block_k)
    return first, _causal_stop(qi, q_block, block_k, nk)


def _visible(row, col, kv_len: int, causal: bool, window):
    """Query ``row`` sees key ``col``: a key that is there, causal: at or
    before the query, with a window: no further back than ``window - 1``."""
    mask = col < kv_len
    if causal:
        mask = mask & (col <= row)
        if window is not None:
            mask = mask & (col > row - window)
    return mask


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                sm_scale: float, block_k: int, kv_len: int, causal: bool,
                q_block: int, window):
    # q_ref: (1, block_q, d); k_ref/v_ref: (1, n, d); o_ref like q_ref;
    # lse_ref: (1, block_q, 8) — 8-lane padded, lane 0 meaningful.
    qi = pl.program_id(1)
    q = q_ref[0]  # native dtype (bf16 in production) -> MXU full rate
    n = k_ref.shape[1]
    nk = n // block_k
    row = qi * q_block + jax.lax.broadcasted_iota(
        jnp.int32, (q.shape[0], block_k), 0)

    def body(ki, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[0, pl.ds(ki * block_k, block_k), :]
        v = v_ref[0, pl.ds(ki * block_k, block_k), :]
        s = sm_scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        col = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[0], block_k), 1)
        # a row whose window starts after this block sees none of it and
        # counts its keys as one each; the next block's alpha, exp(NEG_INF -
        # m), wipes that out (the diagonal block always holds a visible key)
        s = jnp.where(_visible(row, col, kv_len, causal, window), s, NEG_INF)
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    bq, d = q.shape
    acc = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(
        *_key_loop(qi, q_block, block_k, nk, causal, window), body,
        (acc, m0, l0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse = m + jnp.log(l_safe)
    lse_ref[0] = jnp.broadcast_to(lse[:, None], (lse.shape[0], 8))


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, *, sm_scale: float,
                block_q: int, kv_len: int, causal: bool, k_block: int,
                window):
    """One key block of a head against the query blocks that see it: each
    pair's scores, ``P``, ``dP`` and ``dS`` are made once and feed ``dV``,
    ``dK`` (carried over the query blocks) and the head's ``dQ`` (``dq_acc``,
    float32 in VMEM across the key blocks, written at the last). Key blocks
    run in order and query blocks in order inside each, so every gradient
    is summed in the order a query-block-major ``dQ`` loop would sum it."""
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    k = k_ref[0]
    v = v_ref[0]
    n = q_ref.shape[1]
    nq = n // block_q
    col = ki * k_block + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, k.shape[0]), 1)

    def body(qi, carry):
        dk, dv = carry
        rows = pl.ds(qi * block_q, block_q)
        q = q_ref[0, rows, :]
        do = do_ref[0, rows, :]
        lse = lse_ref[0, rows, 0]
        delta = delta_ref[0, rows, 0]
        s = sm_scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        row = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, k.shape[0]), 0)
        p = jnp.where(_visible(row, col, kv_len, causal, window),
                      jnp.exp(s - lse[:, None]), 0.0)
        dv = dv + jax.lax.dot_general(p.astype(do.dtype), do,
                                      (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        dk = dk + jax.lax.dot_general(ds.astype(q.dtype), q,
                                      (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dq_acc[rows, :] = dq_acc[rows, :] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv

    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    # causal: query blocks that end before this key block starts see none
    # of it; with a window, neither do those that start more than
    # ``window - 1`` rows past its last key (the pairs ``_key_loop`` gives
    # a query block, seen from the key side)
    first, stop = 0, nq
    if causal:
        first = (ki * k_block) // block_q
        if window is not None:
            stop = jnp.minimum(
                nq, ((ki + 1) * k_block + window - 2) // block_q + 1)
    dk, dv = jax.lax.fori_loop(first, stop, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _flatten_bh(x):
    b, h, n, d = x.shape
    return x.reshape(b * h, n, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, sm_scale, kv_len, causal, block_q, block_k, window):
    out, _ = _flash_fwd(q, k, v, sm_scale, kv_len, causal, block_q, block_k,
                        window)
    return out


def _flash_fwd(q, k, v, sm_scale, kv_len, causal, block_q, block_k,
               window=None):
    b, h, n, d = q.shape
    group = h // k.shape[1]          # query heads a key/value head
    qf, kf, vf = map(_flatten_bh, (q, k, v))
    grid = (b * h, n // block_q)
    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale,
                               block_k=block_k, kv_len=kv_len, causal=causal,
                               q_block=block_q, window=window)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, n, d), lambda bh, qi: (bh // group, 0, 0)),
            pl.BlockSpec((1, n, d), lambda bh, qi: (bh // group, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 8), lambda bh, qi: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, n, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, n, 8), jnp.float32),
        ],
        interpret=interpret_mode(),
        compiler_params=_compiler_params(),
    )(qf, kf, vf)
    out = checkpoint_name(out.reshape(b, h, n, d), ATTENTION_OUT)
    # the residual is lane 0 alone, (BH, N): the 8 lanes are padded to 128
    # wherever the array stands, as many bytes as ``out`` if it were kept
    lse = checkpoint_name(lse[:, :, 0], ATTENTION_LSE)
    return out, (q, k, v, out, lse)


def _flash_bwd(sm_scale, kv_len, causal, block_q, block_k, window, res, dout):
    q, k, v, out, lse = res
    b, h, n, d = q.shape
    kv_heads = k.shape[1]
    qf, kf, vf = map(_flatten_bh, (q, k, v))
    dof = _flatten_bh(dout)
    of = _flatten_bh(out)
    # delta_i = rowsum(dO_i * O_i); the kernel reads it and the logsumexp
    # as (bh, n, 8), lane 0 meaningful
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1, keepdims=True)
    delta = jnp.broadcast_to(delta, (b * h, n, 8))
    lse = jnp.broadcast_to(lse[:, :, None], (b * h, n, 8))
    dqf, dkf, dvf = _bwd_calls(qf, kf, vf, dof, lse, delta,
                               sm_scale=sm_scale, kv_len=kv_len,
                               causal=causal, block_q=block_q,
                               block_k=block_k, window=window)

    def over_group(x, like):
        # a key/value head's gradient is the sum over the query heads that
        # read it, taken in float32
        if kv_heads == h:
            return x.reshape(b, h, n, d)
        return jnp.sum(x.reshape(b, kv_heads, h // kv_heads, n, d),
                       axis=2, dtype=jnp.float32).astype(like.dtype)
    return dqf.reshape(b, h, n, d), over_group(dkf, k), over_group(dvf, v)


def _bwd_calls(qf, kf, vf, dof, lse, delta, *, sm_scale, kv_len, causal,
               block_q, block_k, out_dtype=None, window=None):
    """The backward pallas_call over flattened (BH, N, D) operands with
    caller-supplied lse/delta (BH, N, 8). Shared by the plain VJP and by
    ring attention's chunk backward (which passes the GLOBAL
    logsumexp/delta so per-chunk gradients sum to the exact full-sequence
    gradient). ``out_dtype`` overrides the gradients' dtype (the ring
    accumulates per-chunk grads in f32, so bf16 round trips per ring
    step would otherwise lose precision). ``kf``, ``vf`` may hold fewer
    heads, (BH / g, N, D): ``dk``, ``dv`` still come back a query head each,
    (BH, N, D), for the caller to sum over a group.

    A program takes one key block of one head and holds, double-buffered,
    the head's whole Q, dO, logsumexp and delta (the last two padded to 128
    lanes), its ``dQ`` output block, and beside them the head's float32
    ``dQ`` sum: at GLM-4.7-Flash's 4,096 tokens of width 256 some 31 MB, at
    8,192 tokens of width 128 (Mellum2) some 38 MB, and as much at width 64
    (Granite 4.0-H), whose rows VMEM pads to 128 lanes."""
    bh, n, d = qf.shape
    group = bh // kf.shape[0]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, sm_scale=sm_scale, block_q=block_q,
                          kv_len=kv_len, causal=causal, k_block=block_k,
                          window=window),
        grid=(bh, n // block_k),
        in_specs=[
            pl.BlockSpec((1, n, d), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh // group, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh // group, ki, 0)),
            pl.BlockSpec((1, n, d), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, n, 8), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, n, 8), lambda bh, ki: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, n, d), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, n, d), out_dtype or qf.dtype),
            jax.ShapeDtypeStruct((bh, n, d), out_dtype or kf.dtype),
            jax.ShapeDtypeStruct((bh, n, d), out_dtype or vf.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((n, d), jnp.float32)],
        interpret=interpret_mode(),
        compiler_params=_compiler_params(),
    )(qf, kf, vf, dof, lse, delta)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    sm_scale: Optional[float] = None,
                    causal: bool = False,
                    window: Optional[int] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K) -> jax.Array:
    """Fused attention. q: (B, H, N, D), k, v: (B, H or H / g, N, D) with any
    N — padded internally to a block multiple; padded KEY positions are
    masked out and padded QUERY rows are dropped on return. D should be
    64/128 for best MXU use. ``window`` needs ``causal``.
    """
    b, h, n, d = q.shape
    if sm_scale is None:
        sm_scale = d ** -0.5
    if window is not None and not causal:
        raise ValueError("a window is a causal layer's")
    if h % k.shape[1] or k.shape[1] != v.shape[1]:
        raise ValueError(f"{h} query heads over {k.shape[1]} key and "
                         f"{v.shape[1]} value heads")
    block_q, block_k, _, (q, k, v) = _blocks_and_pad(n, block_q, block_k,
                                                     q, k, v)
    out = _flash(q, k, v, sm_scale, n, causal, block_q, block_k, window)
    return out[:, :, :n, :]


def flash_attention_with_lse(q: jax.Array, k: jax.Array, v: jax.Array, *,
                             sm_scale: Optional[float] = None,
                             causal: bool = False,
                             block_q: int = DEFAULT_BLOCK_Q,
                             block_k: int = DEFAULT_BLOCK_K):
    """Forward pass returning (out, lse): out (B, H, N, D) and the
    per-row logsumexp (B, H, N) of the scaled scores. This is the hook
    ring attention uses to merge per-chunk kernel results exactly —
    chunks combine as out = Σᵢ outᵢ·exp(lseᵢ − LSE), LSE = logsumexpᵢ.
    Forward-only (no custom VJP through the pair)."""
    b, h, n, d = q.shape
    if sm_scale is None:
        sm_scale = d ** -0.5
    block_q, block_k, n_pad, (q, k, v) = _blocks_and_pad(
        n, block_q, block_k, q, k, v)
    out, res = _flash_fwd(q, k, v, sm_scale, n, causal, block_q, block_k)
    lse = res[4].reshape(b, h, n + n_pad)
    return out[:, :, :n, :], lse[:, :, :n]


def flash_chunk_grads(q: jax.Array, k: jax.Array, v: jax.Array,
                      do: jax.Array, lse: jax.Array, delta: jax.Array, *,
                      sm_scale: Optional[float] = None,
                      block_q: int = DEFAULT_BLOCK_Q,
                      block_k: int = DEFAULT_BLOCK_K):
    """(dq, dk, dv) of attention over ONE KV chunk given the GLOBAL
    softmax statistics: ``lse``/``delta`` (B, H, Nq) are the full-sequence
    logsumexp and rowsum(dO·O). Because dS_ij = P_ij·(dP_ij − delta_i)
    with P taken against the global LSE, per-chunk gradients computed
    this way sum over chunks to the exact full-attention gradient — this
    is ring attention's backward building block (Liu & Abbeel, ring
    attention; same decomposition as FlashAttention-2's dKV pass).

    q/do: (B, H, Nq, D); k/v: (B, H, Nk, D) with Nq == Nk (equal ring
    chunks). Gradients come back in float32 (the caller accumulates
    across ring steps)."""
    b, h, n, d = q.shape
    if k.shape[2] != n:
        raise ValueError(f"ring chunks must be equal: Nq={n} "
                         f"Nk={k.shape[2]}")
    if sm_scale is None:
        sm_scale = d ** -0.5
    block_q, block_k, n_pad, (q, k, v, do) = _blocks_and_pad(
        n, block_q, block_k, q, k, v, do)
    if n_pad:
        pad3 = [(0, 0), (0, 0), (0, n_pad)]
        # padded query rows: do rows are zero, so any finite lse/delta
        # yields zero contributions to dk/dv (ds == 0, p^T do == 0)
        lse = jnp.pad(lse, pad3)
        delta = jnp.pad(delta, pad3)
    np_ = n + n_pad
    qf, kf, vf, dof = map(_flatten_bh, (q, k, v, do))
    lse8 = jnp.broadcast_to(
        lse.astype(jnp.float32).reshape(b * h, np_, 1), (b * h, np_, 8))
    delta8 = jnp.broadcast_to(
        delta.astype(jnp.float32).reshape(b * h, np_, 1), (b * h, np_, 8))
    # f32 gradients: the ring accumulates per-chunk grads across
    # axis_size steps — bf16 round trips each step would compound error
    dqf, dkf, dvf = _bwd_calls(qf, kf, vf, dof, lse8, delta8,
                               sm_scale=sm_scale, kv_len=n, causal=False,
                               block_q=block_q, block_k=block_k,
                               out_dtype=jnp.float32)
    unflat = lambda x: x.reshape(b, h, np_, d)[:, :, :n, :]
    return unflat(dqf), unflat(dkf), unflat(dvf)


def _round_block(n: int, cap: int = 128) -> int:
    """Largest block <= n among ``cap`` halved again and again, >= 8."""
    b = cap
    while b > 8 and b > n:
        b //= 2
    return max(b, 8)


def _blocks_and_pad(n, block_q, block_k, *arrays):
    """Clamp block sizes to the sequence and zero-pad every (B, H, N, D)
    array along N to the blocks' lcm. Returns (block_q, block_k, n_pad,
    padded_arrays) — the one place the padding policy lives."""
    block_q = _round_block(n, block_q)
    block_k = _round_block(n, block_k)
    n_pad = -n % math.lcm(block_q, block_k)
    if n_pad:
        pad = [(0, 0), (0, 0), (0, n_pad), (0, 0)]
        arrays = tuple(jnp.pad(t, pad) for t in arrays)
    return block_q, block_k, n_pad, arrays


# --------------------------------------------------------------------------
# The causal attention core of a decoder: one path a shape, chosen by what
# the code can see.

CAUSAL_BLOCK_Q = 512
CAUSAL_BLOCK_K = 512


def select_path(tokens: int, head_width: int, *,
                initializing: bool = False) -> str:
    """``"fused"`` where the kernels compile (not the CPU backend, where they
    would run interpreted) and the shape fills their tiles (whole 128-row
    blocks, a head width of whole 128-lane tiles or of 64, half of one, in
    a block as wide as the head) with enough rows that the ``T x T`` scores
    are worth keeping off HBM; ``"lax"`` everywhere else, and while
    ``model.init`` runs the layer once, eagerly."""
    covered = tokens % 128 == 0 and tokens >= 512 and (
        head_width % 128 == 0 or head_width == 64)
    return ("fused" if covered and not (initializing or interpret_mode())
            else "lax")


def causal_attention_lax(q: jax.Array, k: jax.Array, v: jax.Array,
                         sm_scale: float,
                         window: Optional[int] = None) -> jax.Array:
    """The lax mathematics, softmax in float32: the CPU path and the
    oracle. q: (B, H, N, D); k, v: (B, H / g, N, D), query head ``j`` reading
    key/value head ``j // g``; with ``window``, query ``i`` sees keys
    ``i - window + 1 .. i``."""
    b, h, n, d = q.shape
    kv = k.shape[1]
    s = jnp.einsum("bkgqd,bkcd->bkgqc", q.reshape(b, kv, h // kv, n, d), k,
                   preferred_element_type=jnp.float32) * sm_scale
    row, col = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    s = jnp.where(_visible(row, col, n, True, window), s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqc,bkcd->bkgqd", p, v).reshape(b, h, n, d)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     sm_scale: float, path: str,
                     window: Optional[int] = None) -> jax.Array:
    """softmax(q k^T * sm_scale, causal, within ``window``) v over q (B, H,
    N, D) and k, v (B, H / g, N, D) of one width, by ``path``
    (``select_path``)."""
    if path == "fused":
        return flash_attention(q, k, v, sm_scale=sm_scale, causal=True,
                               window=window, block_q=CAUSAL_BLOCK_Q,
                               block_k=CAUSAL_BLOCK_K)
    return causal_attention_lax(q, k, v, sm_scale, window)
