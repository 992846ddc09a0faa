"""Jit'd public wrapper: pads to tile multiples, dispatches kernel/oracle.

``use_kernel=True`` runs the Pallas kernel: compiled by Mosaic when lowered
for a TPU, in Pallas's interpreter when lowered for the CPU (see
``repro.kernels.platform``).  ``use_kernel=False`` is the jnp oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas import tpu as pltpu

from repro.core import sketch
from repro.kernels.hamming.kernel import (BC, BQ, ROWS_BK, ROWS_BQ,
                                          hamming_matrix_kernel,
                                          hamming_rows_kernel,
                                          sketch_slots_kernel,
                                          sketch_table_kernel,
                                          sketch_table_vmem_bytes)
from repro.kernels.hamming.ref import hamming_matrix_ref


# What the VMEM route saves per candidate row read, net of its costs beyond
# copying the table at the chip's peak HBM bandwidth.  On a v5e with a
# 64 MiB table the two routes break even near 13.8k rows read, between 8
# and 64 queries of 1,420 candidates (scripts/sketch_read_probe.py).
ROW_READ_SAVING_S = 5.9e-9


def _pad_to(x: jax.Array, m: int, axis: int) -> jax.Array:
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def hamming_matrix(
    queries: jax.Array,
    candidates: jax.Array,
    *,
    use_kernel: bool = False,
) -> jax.Array:
    """Batched Hamming distances between packed uint32 sketch matrices.

    Args:
      queries: (Q, W) uint32.
      candidates: (C, W) uint32.
      use_kernel: route through the Pallas kernel instead of the jnp oracle.

    Returns:
      (Q, C) int32.
    """
    if not use_kernel:
        return hamming_matrix_ref(queries, candidates)
    qn, cn = queries.shape[0], candidates.shape[0]
    qp = _pad_to(queries, BQ, 0)
    cp = _pad_to(candidates, BC, 0)
    out = hamming_matrix_kernel(qp, cp)
    return out[:qn, :cn]


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def hamming_rows(
    queries: jax.Array,
    candidates: jax.Array,
    *,
    use_kernel: bool = False,
) -> jax.Array:
    """(Q, W) vs per-query (Q, K, W) packed sketches -> (Q, K) int32."""
    if not use_kernel:
        x = jnp.bitwise_xor(queries[:, None, :], candidates)
        return jnp.sum(lax.population_count(x).astype(jnp.int32), axis=-1)
    qn, k = candidates.shape[:2]
    # Word-major candidates (Q, W, K): K on lanes (see kernel.py).
    cp = _pad_to(_pad_to(jnp.swapaxes(candidates, 1, 2), ROWS_BQ, 0),
                 _rows_bk(k), 2)
    return hamming_rows_word_major(queries, cp)[:qn, :k]


def _rows_bk(k: int) -> int:
    """The row kernels' candidate tile for K candidates per query."""
    return min(ROWS_BK, -(-k // 128) * 128)


@jax.jit
def hamming_rows_word_major(queries: jax.Array, candidates: jax.Array
                            ) -> jax.Array:
    """(Q, W) vs word-major (Q', W, K') candidates, as :func:`gather_sketches`
    returns them (Q' ≥ Q and K' padded to the row tiles) -> (Q', K') int32,
    always through the Pallas kernel."""
    qp = _pad_to(queries, ROWS_BQ, 0)[:, :, None]
    return hamming_rows_kernel(qp, candidates,
                               bk=_rows_bk(candidates.shape[2]))


def table_in_vmem(table_bytes: int, reads: int, lanes: int) -> bool:
    """Whether stage 1 reads ``reads`` candidate rows from a blocked table
    of ``table_bytes`` copied into VMEM, rather than gathering them from
    HBM: the table fits the chip's VMEM beside the kernel's blocks, and
    the rows read save more than copying it in costs.  Off a TPU (no
    VMEM) the answer is no."""
    try:
        info = pltpu.get_tpu_info()
    except ValueError:  # not a TPU
        return False
    if sketch_table_vmem_bytes(table_bytes, lanes) > info.vmem_capacity_bytes:
        return False
    copy_s = table_bytes / info.mem_bw_bytes_per_second
    return copy_s <= reads * ROW_READ_SAVING_S


@functools.partial(jax.jit, static_argnames=("w",))
def gather_sketches(table: jax.Array, pos: jax.Array, *, w: int) -> jax.Array:
    """Candidate sketches from an index's resident sketch table, word-major.

    Args:
      table: (R, L) uint32 blocked table, or (n, w) rows
        (``repro.core.sketch``).
      pos: (Q, K) int32 master positions.
      w: words per sketch.

    Returns:
      (Q', w, K') uint32 with Q' and K' padded to the row kernels' tiles;
      padded entries hold position 0's sketch.

    A blocked table is read in VMEM (``sketch_table_kernel``) where
    :func:`table_in_vmem` says so; otherwise XLA gathers one whole L-lane
    row per candidate from HBM and ``sketch_slots_kernel`` keeps its slot.
    """
    bk = _rows_bk(pos.shape[1])
    pos = _pad_to(_pad_to(pos, ROWS_BQ, 0), bk, 1)
    if not sketch.is_blocked(table, w):
        return jnp.swapaxes(table[pos], 1, 2)
    s = sketch.slot_words(w)
    if table_in_vmem(table.nbytes, pos.size, table.shape[1]):
        rows, slots = sketch.row_and_slot(table, pos, w)
        return sketch_table_kernel(rows, slots, table, w=w, s=s, bk=bk)
    rows, slots = sketch.gather_rows(table, pos, w)
    return sketch_slots_kernel(slots, rows, w=w, s=s, bk=bk)
