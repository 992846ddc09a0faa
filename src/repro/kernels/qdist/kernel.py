"""Pallas TPU kernel: fused 4-bit dequantization + asymmetric L2 distance.

Final-stage ranking (paper §3.1): fp32 queries against 4-bit-quantized
database vectors.  The CPU implementation gathers per-dim LUT entries —
serial scalar work.  The TPU formulation reconstructs the candidate tile
with 16 vectorized selects (one per code level; no gathers) and computes

    d²(q, r) = ‖q‖² − 2·q·rᵀ + ‖r‖²

with the cross term on the MXU — this is the deliberate CPU→TPU algorithm
change recorded in DESIGN.md §2.

Three variants:
  * ``qdist_u8_kernel``    — codes arrive as (C, d) uint8 (VMEM feed 1 B/dim).
  * ``qdist_packed_kernel``— codes arrive nibble-packed (C, d//8) uint32
    (VMEM/HBM feed 0.5 B/dim — the memory-roofline winner at 23M
    candidates).  Dims are processed in nibble-extraction order
    (j = 8·w + s scanned s-major), so queries/centroids must be permuted by
    ``packed_dim_order`` first; distance is order-invariant so the result
    is identical.  The cross term becomes 8 accumulated (BQ,W)@(W,BC)
    matmuls.
  * ``qdist_packed_windows_kernel`` — the stage-2 serving shape: every query
    brings its OWN candidate set (the ±h master-order windows gathered by
    the fused search path), so there is no matmul to share between
    queries.  Candidates arrive word-major, (Q, W, C) uint32: candidates on
    the 128 lanes, packed words on sublanes.  The query and the centroid
    table arrive split by nibble, (Q, 8, W, 1) and (8·L, W, 1), so nibble
    s of every word lines up with its dims without any lane-offset slice.
    Per nibble the kernel reconstructs a (BQ, W, BC) tile, and the cross
    term and ‖r‖² are sums over the W sublanes.

Tiling: grid (Q/BQ, C/BC); VMEM per step ≈ BQ·d·4 + BC·d (+ recon BC·d·4)
+ BQ·BC·4 ≈ 0.6 MB at (128, 128, d=384) — well inside 16 MB VMEM, sized so
the MXU K-dim (=d) is a multiple of 128 after ops.py padding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.platform import pallas_call

BQ = 128
BC = 128
# Windows kernel tiles: 8 queries × up to 512 candidates per step.
WIN_BQ = 8
WIN_BC = 512


def _reconstruct(codes_i32: jax.Array, cents: jax.Array, levels: int) -> jax.Array:
    """Dequantize (BC, D) int32 codes against (D, L) centroids, no gathers."""
    recon = jnp.zeros(codes_i32.shape, jnp.float32)
    for l in range(levels):
        recon = jnp.where(codes_i32 == l, cents[None, :, l], recon)
    return recon


def _qdist_u8_kernel(q_ref, c_ref, cent_ref, out_ref, *, levels: int):
    q = q_ref[...]                      # (BQ, D) f32
    codes = c_ref[...].astype(jnp.int32)  # (BC, D)
    cents = cent_ref[...]               # (D, L) f32
    recon = _reconstruct(codes, cents, levels)
    cross = jax.lax.dot_general(
        q, recon, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (BQ, BC)
    qsq = jnp.sum(q * q, axis=1, keepdims=True)         # (BQ, 1)
    rsq = jnp.sum(recon * recon, axis=1, keepdims=True)  # (BC, 1)
    out_ref[...] = qsq - 2.0 * cross + rsq.T


def _qdist_packed_kernel(q_ref, c_ref, cent_ref, out_ref, *, levels: int):
    q = q_ref[...]                       # (BQ, 8W) f32, permuted dim order
    packed = c_ref[...]                  # (BC, W) uint32
    cents = cent_ref[...]                # (8W, L) f32, permuted dim order
    w = packed.shape[1]
    acc = jnp.zeros((q.shape[0], packed.shape[0]), jnp.float32)
    rsq = jnp.zeros((packed.shape[0], 1), jnp.float32)
    for s in range(8):
        nib = ((packed >> jnp.uint32(4 * s)) & jnp.uint32(0xF)).astype(jnp.int32)
        cent_s = jax.lax.dynamic_slice_in_dim(cents, s * w, w, axis=0)  # (W, L)
        recon = _reconstruct(nib, cent_s, levels)  # (BC, W)
        q_s = jax.lax.dynamic_slice_in_dim(q, s * w, w, axis=1)  # (BQ, W)
        acc += jax.lax.dot_general(
            q_s, recon, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        rsq += jnp.sum(recon * recon, axis=1, keepdims=True)
    qsq = jnp.sum(q * q, axis=1, keepdims=True)
    out_ref[...] = qsq - 2.0 * acc + rsq.T


def _qdist_packed_windows_kernel(q_ref, c_ref, cent_ref, out_ref, *, levels: int):
    packed = c_ref[...]                  # (BQ, W, BC) uint32, word-major
    acc = rsq = qsq = None
    for s in range(8):
        nib = ((packed >> jnp.uint32(4 * s)) & jnp.uint32(0xF)).astype(jnp.int32)
        recon = jnp.zeros(nib.shape, jnp.float32)
        for l in range(levels):  # centroid column (W, 1) of nibble s, level l
            recon = jnp.where(nib == l, cent_ref[s * levels + l][None], recon)
        q_s = q_ref[:, s]                # (BQ, W, 1) dims 8·w + s
        cross = jnp.sum(q_s * recon, axis=1)        # (BQ, BC)
        r = jnp.sum(recon * recon, axis=1)          # (BQ, BC)
        qq = jnp.sum(q_s * q_s, axis=1)             # (BQ, 1)
        acc = cross if acc is None else acc + cross
        rsq = r if rsq is None else rsq + r
        qsq = qq if qsq is None else qsq + qq
    out_ref[...] = qsq - 2.0 * acc + rsq


def packed_dim_order(d: int) -> np.ndarray:
    """Dim permutation matching nibble-extraction order (s-major, w-minor).

    ``pack_codes`` puts original dim j = 8·w + s into nibble s of word w;
    the packed kernel scans s = 0..7 emitting all words per s, i.e. column
    j' = s·W + w corresponds to original dim 8·w + s.
    """
    w = d // 8
    s, ww = np.divmod(np.arange(d), w)
    return (8 * ww + s).astype(np.int32)


@functools.partial(jax.jit, static_argnames=("levels", "bq", "bc"))
def qdist_u8_kernel(
    queries: jax.Array,
    codes: jax.Array,
    centroids: jax.Array,
    *,
    levels: int = 16,
    bq: int = BQ,
    bc: int = BC,
) -> jax.Array:
    """(Q, D) f32 × (C, D) uint8 codes × (D, L) centroids -> (Q, C) f32 d²."""
    qn, d = queries.shape
    cn = codes.shape[0]
    grid = (qn // bq, cn // bc)
    return pallas_call(
        functools.partial(_qdist_u8_kernel, levels=levels),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bc, d), lambda i, j: (j, 0)),
            pl.BlockSpec((d, levels), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qn, cn), jnp.float32),
    )(queries, codes, centroids)


@functools.partial(jax.jit, static_argnames=("levels", "bq", "bc"))
def qdist_packed_kernel(
    queries_perm: jax.Array,
    packed: jax.Array,
    centroids_perm: jax.Array,
    *,
    levels: int = 16,
    bq: int = BQ,
    bc: int = BC,
) -> jax.Array:
    """Packed variant; queries/centroids pre-permuted by packed_dim_order."""
    qn, d = queries_perm.shape
    cn, w = packed.shape
    assert d == 8 * w, (d, w)
    grid = (qn // bq, cn // bc)
    return pallas_call(
        functools.partial(_qdist_packed_kernel, levels=levels),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bc, w), lambda i, j: (j, 0)),
            pl.BlockSpec((d, levels), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qn, cn), jnp.float32),
    )(queries_perm, packed, centroids_perm)


@functools.partial(jax.jit, static_argnames=("levels", "bq", "bc"))
def qdist_packed_windows_kernel(
    queries_split: jax.Array,
    packed_windows: jax.Array,
    centroids_split: jax.Array,
    *,
    levels: int = 16,
    bq: int = WIN_BQ,
    bc: int = WIN_BC,
) -> jax.Array:
    """Per-query candidate windows: (Q, 8, W, 1) f32 × (Q, W, C) uint32 ->
    (Q, C) f32 d².

    ``queries_split[q, s, w]`` is dim 8·w + s of query q and
    ``centroids_split[s·L + l, w]`` is centroid ``l`` of that dim (the
    layout ``pack_codes`` gives nibble s of word w).  Q must be a multiple
    of ``bq`` and C of ``bc`` (ops.py pads).
    """
    qn, _, w, _ = queries_split.shape
    cn = packed_windows.shape[2]
    grid = (qn // bq, cn // bc)
    return pallas_call(
        functools.partial(_qdist_packed_windows_kernel, levels=levels),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, 8, w, 1), lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((bq, w, bc), lambda i, j: (i, 0, j)),
            pl.BlockSpec((8 * levels, w, 1), lambda i, j: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qn, cn), jnp.float32),
    )(queries_split, packed_windows, centroids_split)
