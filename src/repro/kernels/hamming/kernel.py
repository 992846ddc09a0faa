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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

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
