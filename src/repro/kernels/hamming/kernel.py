"""Pallas TPU kernel: batched Hamming distance over packed binary sketches.

Stage-2 of the paper's Task-1 pipeline is a (Q queries × C candidates)
Hamming-distance filter over 384-bit sketches (12 uint32 words).  At
challenge scale this touches 23M × 48 B = 1.1 GB of sketch data per query
batch — memory-bound, so the kernel's job is to stream sketch tiles through
VMEM once while every query tile in VMEM is scored against them.

Tiling: grid (Q/BQ, C/BC); per step the kernel holds a (BQ, W) query tile
and a (BC, W) candidate tile in VMEM and emits a (BQ, BC) int32 tile.  The
XOR+popcount runs on the VPU; popcount is SWAR bit-twiddling (portable to
interpret mode and Mosaic alike).  W (words per sketch) stays un-tiled: it
is ≤ 16 for every config we ship (512-bit sketches).  Popcounts are summed
in int32: Mosaic has no reduction over unsigned integers.

The row-wise kernel (stage 1 of Algorithm 1) takes its candidates
word-major, ``(Q, W, K)``: candidates on the 128 lanes, the W sketch words
on sublanes.  Laid out ``(Q, K, W)`` a W=12 word row would pad to 128 lanes
and a (BQ, 1420, 12) block would need ~93 MB of VMEM.

On a TPU stage 1 reads those candidates from the index's blocked sketch
table (``repro.core.sketch``): each sketch sits in an S-word slot (S = 16
for W = 12) and 128 / S of them share a 128-lane row.  An ``(n, W)`` table
is stored point-minor on a TPU (words on sublanes, points on lanes), so
XLA gathered each candidate as W element reads; a blocked row is one
contiguous read.  :func:`sketch_table_kernel` copies a table into VMEM
once per call and reads each candidate's row there with one vector load;
where the table does not fit VMEM, or too few candidates are read to pay
for the copy (``ops.table_in_vmem``), XLA gathers the rows from HBM and
:func:`sketch_slots_kernel` keeps each candidate's slot.  Both lay the
slots out word-major for the row kernel: per query they transpose the
``(BK, 128)`` row block on the XLU (candidates onto lanes), so no
relayout of the wide rows is left to XLA, whose own slot select relays
the gathered ``(Q·K, 128)`` rows out.  Times per route on a v5e: PERF.md.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call

# Default tile sizes: (8, 128) is the fp32/int32 minimum tile; 128×128
# output tiles keep the VMEM working set at
#   BQ·W + BC·W + BQ·BC words  ≈  128·16·2·4B + 64KB ≈ 320 KB  « 16 MB VMEM.
BQ = 128
BC = 128
# Row-wise kernel tiles: 8 queries × up to 512 candidates per step, i.e. a
# (8, W≤16, 512) uint32 candidate block of at most 256 KB.
ROWS_BQ = 8
ROWS_BK = 512


def _popcount32(v: jax.Array) -> jax.Array:
    """SWAR popcount of a uint32 vector (Hacker's Delight 5-2)."""
    v = v - ((v >> jnp.uint32(1)) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> jnp.uint32(2)) & jnp.uint32(0x33333333))
    v = (v + (v >> jnp.uint32(4))) & jnp.uint32(0x0F0F0F0F)
    return (v * jnp.uint32(0x01010101)) >> jnp.uint32(24)


def _hamming_kernel(q_ref, c_ref, out_ref):
    q = q_ref[...]  # (BQ, W) uint32
    c = c_ref[...]  # (BC, W) uint32
    x = jnp.bitwise_xor(q[:, None, :], c[None, :, :])  # (BQ, BC, W)
    out_ref[...] = jnp.sum(_popcount32(x).astype(jnp.int32), axis=-1)


@functools.partial(jax.jit, static_argnames=("bq", "bc"))
def hamming_matrix_kernel(
    queries: jax.Array,
    candidates: jax.Array,
    *,
    bq: int = BQ,
    bc: int = BC,
) -> jax.Array:
    """(Q, W) × (C, W) packed uint32 sketches -> (Q, C) int32 Hamming.

    Q and C must be multiples of the tile sizes (ops.py pads).
    """
    qn, w = queries.shape
    cn, _ = candidates.shape
    grid = (qn // bq, cn // bc)
    return pallas_call(
        _hamming_kernel,
        name="hamming_matrix",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, w), lambda i, j: (i, 0)),
            pl.BlockSpec((bc, w), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qn, cn), jnp.int32),
    )(queries, candidates)


def _hamming_rows_kernel(q_ref, c_ref, out_ref):
    q = q_ref[...]  # (BQ, W, 1)
    c = c_ref[...]  # (BQ, W, BK)
    x = jnp.bitwise_xor(c, q)
    out_ref[...] = jnp.sum(_popcount32(x).astype(jnp.int32), axis=1)


@functools.partial(jax.jit, static_argnames=("bq", "bk"))
def hamming_rows_kernel(
    queries: jax.Array,      # (Q, W, 1) uint32
    candidates: jax.Array,   # (Q, W, K) uint32 — per-query sets, word-major
    *,
    bq: int = ROWS_BQ,
    bk: int = ROWS_BK,
) -> jax.Array:
    """Row-wise Hamming: each query scored against ITS OWN K candidates —
    the exact stage-1 access pattern of Algorithm 1 (forest windows are
    per-query).  Q must be a multiple of bq and K of bk (ops.py pads and
    re-lays the candidates word-major)."""
    qn, w, _ = queries.shape
    k = candidates.shape[2]
    grid = (qn // bq, k // bk)
    return pallas_call(
        _hamming_rows_kernel,
        name="hamming_rows",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, w, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((bq, w, bk), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((bq, bk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qn, k), jnp.int32),
    )(queries, candidates)


def _pick_slots(rows, slot, s: int, w: int):
    """(BK, L) rows and their (1, BK) slots -> (w, BK) sketches, word-major:
    the row block is transposed on the XLU (lanes onto sublanes), then each
    candidate's S words are selected by its slot."""
    words = rows.T                             # (L, BK)
    picked = words[0:s]
    for j in range(1, words.shape[0] // s):
        picked = jnp.where(slot == j, words[j * s:(j + 1) * s], picked)
    return picked[:w]


def _sketch_slots_kernel(slot_ref, rows_ref, out_ref, *, s):
    bq, w, _ = out_ref.shape
    for i in range(bq):
        out_ref[i] = _pick_slots(rows_ref[i], slot_ref[pl.ds(i, 1), :], s, w)


@functools.partial(jax.jit, static_argnames=("w", "s", "bq", "bk"))
def sketch_slots_kernel(
    slots: jax.Array,   # (Q, K) int32 slot of each candidate in its row
    rows: jax.Array,    # (Q, K, L) uint32 blocked-table row per candidate
    *,
    w: int,
    s: int,
    bq: int = ROWS_BQ,
    bk: int = ROWS_BK,
) -> jax.Array:
    """Each candidate's ``w``-word sketch from its gathered row, word-major
    ``(Q, w, K)`` for :func:`hamming_rows_kernel`.  ``s`` is the slot width;
    Q must be a multiple of bq and K of bk (ops.py pads)."""
    qn, k, lanes = rows.shape
    return pallas_call(
        functools.partial(_sketch_slots_kernel, s=s),
        name="sketch_slots",
        grid=(qn // bq, k // bk),
        in_specs=[
            pl.BlockSpec((bq, bk), lambda i, j: (i, j)),
            pl.BlockSpec((bq, bk, lanes), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, w, bk), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((qn, w, k), rows.dtype),
    )(slots, rows)


# Rows fetched per iteration of the table kernel's row loop.
_FETCH_UNROLL = 8
# VMEM the table kernel leaves to Mosaic beyond its table and row buffer.
_TABLE_VMEM_HEADROOM = 32 << 20


def sketch_table_vmem_bytes(table_bytes: int, lanes: int,
                            bq: int = ROWS_BQ, bk: int = ROWS_BK) -> int:
    """VMEM :func:`sketch_table_kernel` asks for with a table of
    ``table_bytes``: the table, one step's rows, and headroom."""
    return table_bytes + bq * bk * lanes * 4 + _TABLE_VMEM_HEADROOM


def _sketch_table_kernel(row_ref, slot_ref, table_hbm, out_ref, table, buf,
                         sem, *, s):
    bq, w, bk = out_ref.shape

    @pl.when(pl.program_id(0) == 0)
    def _():
        copy = pltpu.make_async_copy(table_hbm, table, sem.at[0])
        copy.start()
        copy.wait()

    def fetch(r, carry):
        for u in range(_FETCH_UNROLL):
            i = r * _FETCH_UNROLL + u
            buf[pl.ds(i, 1), :] = table[pl.ds(row_ref[i], 1), :]
        return carry

    lax.fori_loop(0, bq * bk // _FETCH_UNROLL, fetch, 0)
    for i in range(bq):
        out_ref[i] = _pick_slots(buf[pl.ds(i * bk, bk), :],
                                 slot_ref[pl.ds(i, 1), :], s, w)


@functools.partial(jax.jit, static_argnames=("w", "s", "bq", "bk"))
def sketch_table_kernel(
    rows: jax.Array,    # (Q, K) int32 table row of each candidate
    slots: jax.Array,   # (Q, K) int32 slot of each candidate in its row
    table: jax.Array,   # (R, L) uint32 blocked sketch table
    *,
    w: int,
    s: int,
    bq: int = ROWS_BQ,
    bk: int = ROWS_BK,
) -> jax.Array:
    """Candidate sketches read from a blocked table held in VMEM, word-major
    ``(Q, w, K)`` for :func:`hamming_rows_kernel`.

    The first grid step copies the whole table into VMEM (one contiguous
    DMA); every step then reads its ``bq * bk`` candidates' rows with one
    dynamic-sublane vector load each, their row indices from SMEM, and
    keeps each candidate's slot.  For tables that fit VMEM next to the
    blocks (ops.py decides).  Q must be a multiple of bq and K of bk.
    """
    qn, k = rows.shape
    n_rows, lanes = table.shape
    nk = k // bk
    # The row indices reach SMEM one step's block at a time, query-major.
    flat = rows.reshape(qn // bq, bq, nk, bk).transpose(0, 2, 1, 3)
    return pallas_call(
        functools.partial(_sketch_table_kernel, s=s),
        name="sketch_table",
        grid=(qn // bq * nk,),
        in_specs=[
            pl.BlockSpec((bq * bk,), lambda t: (t,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((bq, bk), lambda t: (t // nk, t % nk)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((bq, w, bk), lambda t: (t // nk, 0, t % nk)),
        out_shape=jax.ShapeDtypeStruct((qn, w, k), table.dtype),
        scratch_shapes=[
            pltpu.VMEM((n_rows, lanes), table.dtype),
            pltpu.VMEM((bq * bk, lanes), table.dtype),
            pltpu.SemaphoreType.DMA((1,)),
        ],
        # Sequential steps: the table copied at step 0 serves every step.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=sketch_table_vmem_bytes(table.nbytes, lanes,
                                                     bq, bk),
        ),
    )(flat.reshape(-1), slots, table)
