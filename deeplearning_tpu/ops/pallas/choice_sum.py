"""The token side of the expert layer's row buffer, reading only what is here.

``parallel/moe.py``'s row buffer holds, for the experts this chip holds, one
row a (token, choice); a token's output is the weighted sum of its choices'
rows: ``out[t] = sum_j w[t, j] * src[slot[t, j]]`` over the choices that are
``here``, in float32, (C, D) -> (T, D). The lax path (``moe._sum_choices``,
the oracle) makes ``top_k`` gathers of T rows whether a choice is here or not
(on a chip that holds 16 of 64 experts three reads in four are of absent
choices) and XLA writes each gather to HBM before one fusion sums them.

``choice_sum`` fetches from HBM the rows of the choices that are here and no
other: a row DMA each, from the buffer left in HBM into a VMEM staging
buffer, and a (token block, D) float32 accumulator summed in the lax path's
order, choice 0 first, so the result is its bit for bit. Outside the kernel
one sort puts each (token block, choice)'s present choices first, so its loop
of copies reads no absent choice and takes no branch (a loop over every
choice with a branch each cost 30 ns a choice on a v5e, more than XLA's
gathers: PERF.md §6, PR 37). The copies of the next choice (after the last,
of the next block's first) are in flight while the current one is weighted
and added; the waits count the rows started.

Layout. Mosaic moves whole tiles, and a bfloat16 row is half of each 32-bit
word of a packed tile (a DMA of one row of XLA's ``(C, D)`` array is refused,
float32 too: "slice shape ... must be aligned to tiling (8)"), so the buffer
reaches the kernel as 32-bit words, each a bfloat16 of column ``i`` in its
low half and one of column ``i + D / 2`` in its high half, ``(C, 1, D / 2)``:
a row is one ``(1, 128)``-tiled run of words and its own DMA. A second Pallas
call makes that copy of the rows a slot can name, in one pass (XLA split the
same packing into two or three, however it was written); in VMEM a shift and
a mask give the two halves back as float32, exactly the bfloat16 values,
added to the left and the right half of the accumulator.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import interpret_mode

# the lists of present choices are read a block at a time from SMEM, whose 1-D
# blocks XLA tiles by 1,024 words: a block's ``tokens x top_k`` is whole tiles
_SMEM_WORDS = 1024
# VMEM a program may hold: the accumulator block double-buffered (8 bytes a
# token and column), two staging buffers (4) and the weights and slots (2,048
# bytes a token: two (tb, k) blocks, double-buffered, 128 lanes wide)
_VMEM_BUDGET = 32 * 2 ** 20
_VMEM_LIMIT = 48 * 2 ** 20
# rows of the block one step of the weighted sum takes (a loop, not one
# straight run of code over the whole block: that took Mosaic 74 s)
_ROWS = 32
# rows of the buffer a step of the packing takes
_PACK_ROWS = 512
# a present choice reaches the kernel as ``slot << _TOKEN_BITS | token``, the
# token's row in its block (up to 1,024): slots up to 2 ** 21
_TOKEN_BITS = 10


def token_block(tokens: int, top_k: int, width: int) -> int | None:
    """Tokens of a program: the most, up to 1,024, that divide ``tokens``,
    hold whole SMEM tiles of slots and fit the VMEM budget; None if no block
    does."""
    for tb in (1024, 512, 256, 128, 64, 32):
        if (tokens % tb == 0 and tb * top_k % _SMEM_WORDS == 0
                and tb * (12 * width + 2048) <= _VMEM_BUDGET
                and tokens * top_k < 2 ** (31 - _TOKEN_BITS)):
            return tb
    return None


def select_path(tokens: int, top_k: int, width: int, dtype, *,
                initializing: bool = False) -> str:
    """``"fused"`` where the kernel compiles (not the CPU backend, where it
    would run interpreted), the rows are bfloat16 of whole 256-lane pairs
    and the tokens fill whole blocks; ``"lax"`` everywhere else and while
    ``model.init`` runs the layer once, eagerly."""
    covered = (jnp.dtype(dtype) == jnp.bfloat16 and width % 256 == 0
               and token_block(tokens, top_k, width) is not None)
    return ("fused" if covered and not (initializing or interpret_mode())
            else "lax")


def _pack_kernel(blocks_ref, src_ref, out_ref):
    # src_ref: (rb, 2h) bfloat16; out_ref: (rb, 1, h) uint32
    @pl.when(pl.program_id(0) < blocks_ref[0])
    def _():
        h = out_ref.shape[-1]
        bits = [pltpu.bitcast(src_ref[:, half].astype(jnp.float32), jnp.uint32)
                for half in (slice(0, h), slice(h, 2 * h))]
        out_ref[:, 0, :] = (bits[0] >> 16) | (bits[1] & jnp.uint32(0xFFFF0000))


def _pack(src: jax.Array, rows: jax.Array) -> jax.Array:
    """(C, D) bfloat16 -> (C, 1, D / 2) uint32, column ``i`` in the low half
    and column ``i + D / 2`` in the high half of word ``i``, for the first
    ``rows`` rows (the ones a slot can name; the others are left unwritten):
    one pass over them, read in XLA's tiles, written a row a tile row."""
    c, d = src.shape
    rb = _PACK_ROWS if c % _PACK_ROWS == 0 else c
    last = lambda i, blocks: (jnp.minimum(i, jnp.maximum(blocks[0] - 1, 0)),)
    return pl.pallas_call(
        _pack_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(c // rb,),
            in_specs=[pl.BlockSpec((rb, d), lambda i, b: (*last(i, b), 0))],
            out_specs=pl.BlockSpec((rb, 1, d // 2),
                                   lambda i, b: (*last(i, b), 0, 0))),
        out_shape=jax.ShapeDtypeStruct((c, 1, d // 2), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret_mode(),
        name="choice_sum_pack",
    )(jnp.reshape(-(-rows // rb), (1,)).astype(jnp.int32), src)


def _kernel(counts_ref, lists_ref, next_ref, table_ref, w_ref, src_hbm,
            out_ref, stage, sems):
    # counts_ref: (blocks * k,) int32, scalar-prefetched: the present choices
    # of each (block, choice); lists_ref / next_ref: (k * tb,) int32 in SMEM,
    # this block's and the next block's present choices, choice by choice,
    # each ``slot << _TOKEN_BITS | token``; table_ref: (tb, k) int32 in VMEM,
    # the slot or -1 where absent; w_ref: (tb, k) float32; src_hbm: (C, 1, h)
    # uint32 in HBM; out_ref: (tb, 2h) float32; stage: (2, tb, 1, h) uint32;
    # sems: 2 DMA semaphores
    i, blocks = pl.program_id(0), pl.num_programs(0)
    tb, k = table_ref.shape
    h = stage.shape[-1]
    low_bits = (1 << _TOKEN_BITS) - 1

    def row(entry, buf):
        return pltpu.make_async_copy(
            src_hbm.at[entry >> _TOKEN_BITS],
            stage.at[buf, entry & low_bits], sems.at[buf])

    def fetch(lists, n, j, buf):
        """Start the copies of choice ``j``'s ``n`` present rows into
        ``buf``: eight at a time, no branch, then the rest."""
        def eight(g, carry):
            entries = [lists[j * tb + 8 * g + u] for u in range(8)]
            for entry in entries:
                row(entry, buf).start()
            return carry

        def one(q, carry):
            row(lists[j * tb + q], buf).start()
            return carry
        jax.lax.fori_loop(0, n // 8, eight, 0)
        jax.lax.fori_loop(n // 8 * 8, n, one, 0)

    def wait(n, buf):
        def one(_, carry):
            row(0, buf).wait()
            return carry
        jax.lax.fori_loop(0, n, one, 0)

    def add(j, buf, r, carry):
        """``out += where(here, row, 0) * w`` for choice ``j``, rows
        ``r * _ROWS ..`` of the block."""
        rows = pl.ds(pl.multiple_of(r * _ROWS, _ROWS), _ROWS)
        here = table_ref[rows, j:j + 1] >= 0
        w = w_ref[rows, j:j + 1]
        words = stage[buf, rows, 0, :]
        low = pltpu.bitcast(words << 16, jnp.float32)
        high = pltpu.bitcast(words & jnp.uint32(0xFFFF0000), jnp.float32)
        out_ref[rows, :h] += jnp.where(here, low, 0.0) * w
        out_ref[rows, h:] += jnp.where(here, high, 0.0) * w
        return carry

    @pl.when(i == 0)
    def _():
        fetch(lists_ref, counts_ref[0], 0, 0)

    out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32)
    for j in range(k):
        buf = (i * k + j) % 2
        if j + 1 < k:
            fetch(lists_ref, counts_ref[i * k + j + 1], j + 1, 1 - buf)
        else:
            @pl.when(i + 1 < blocks)
            def _():
                fetch(next_ref, counts_ref[(i + 1) * k], 0, 1 - buf)
        wait(counts_ref[i * k + j], buf)
        jax.lax.fori_loop(0, tb // _ROWS, functools.partial(add, j, buf), 0)


def choice_sum(src: jax.Array, slot: jax.Array, here: jax.Array,
               w: jax.Array) -> jax.Array:
    """``sum_j w[t, j] * src[slot[t, j]]`` over the choices that are
    ``here``, in float32: (C, D) bfloat16 rows, (T, k) slots, presence and
    weights -> (T, D), bit for bit ``moe._sum_choices``. Rows of absent
    choices are never read (their slots may be anything)."""
    t, k = slot.shape
    c, d = src.shape
    tb = token_block(t, k, d)
    blocks = t // tb
    table = jnp.where(here, slot, -1).astype(jnp.int32)
    # each (block, choice)'s present choices first, as slot and token in one
    # word: the kernel's loop of copies reads no absent choice and takes no
    # branch (the order inside a choice is the sum's business of no one)
    by_choice = lambda a: a.reshape(blocks, tb, k).transpose(0, 2, 1)
    token = jnp.arange(t, dtype=jnp.int32)[:, None] % tb
    absent, lists = jax.lax.sort(
        (by_choice(~here).astype(jnp.int32),
         by_choice((table << _TOKEN_BITS) | token)),
        dimension=2, num_keys=1, is_stable=False)
    counts = jnp.sum(1 - absent, axis=2, dtype=jnp.int32).reshape(-1)
    smem = functools.partial(pl.BlockSpec, (k * tb,),
                             memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(blocks,),
            in_specs=[
                smem(lambda i, n: (i,)),
                smem(lambda i, n: (jnp.minimum(i + 1, blocks - 1),)),
                pl.BlockSpec((tb, k), lambda i, n: (i, 0)),
                pl.BlockSpec((tb, k), lambda i, n: (i, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((tb, d), lambda i, n: (i, 0)),
            scratch_shapes=[pltpu.VMEM((2, tb, 1, d // 2), jnp.uint32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((t, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret_mode(),
        name="choice_sum",
    )(counts, lists.reshape(-1), lists.reshape(-1), table,
      w.astype(jnp.float32), _pack(src, jnp.max(table) + 1))
