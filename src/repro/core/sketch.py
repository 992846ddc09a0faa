"""Binary sketches = the shared MSBs of the 4-bit quantizer (paper §3.1).

For d=384 dims the sketch is exactly 384 bits: bit i is ``x_i >= median_i``,
which is also the MSB of dimension i's 4-bit code — "one bit is shared with
the sketch".  Hamming distance = XOR + popcount over packed uint32 lanes.

An index on a TPU keeps its sketches in a **blocked table**: each W-word
sketch is padded to a slot of S words (the next power of two ≥ W; 16 for
W = 12), and ``L / S`` consecutive master positions share one row of
L = max(128, S) lanes, so the table is ``(ceil(n·S / L), L)`` uint32,
row-major, with zero pad slots and pad words.  Position ``p`` lives in row
``p // (L/S)``, slot ``p % (L/S)``.  A TPU stores an ``(n, W)`` table
point-minor (the W words on sublanes, points on lanes), so gathering one
sketch from it is W element reads; a blocked row is one contiguous 512 B
read.  The same device bytes either way: the point-minor table pads W = 12
sublanes to 16.  Elsewhere an ``(n, W)`` row is already one contiguous read
and the slots would only pad it, so an index off a TPU keeps the ``(n, W)``
rows (:func:`resident_table`).  Readers tell the two apart by shape: a
table is blocked iff its rows are not W words wide (for W = 128 the two
layouts are the same array).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.quantize import Quantizer

__all__ = [
    "sketch_words",
    "make_sketches",
    "sketches_from_codes",
    "hamming_distance",
    "slot_words",
    "table_lanes",
    "block_sketches",
    "resident_table",
    "is_blocked",
    "unblock_sketches",
    "row_and_slot",
    "gather_rows",
    "read_blocked",
    "read_sketches",
]


def sketch_words(d: int) -> int:
    return -(-d // 32)


def slot_words(w: int) -> int:
    """Words per slot of the blocked table: the next power of two ≥ w."""
    return 1 << max(w - 1, 0).bit_length()


def table_lanes(w: int) -> int:
    """Lanes per row of the blocked table of w-word sketches."""
    return max(128, slot_words(w))


def block_sketches(sketches: jax.Array) -> jax.Array:
    """(n, W) sketches -> the blocked ``(ceil(n·S/L), L)`` table."""
    n, w = sketches.shape
    s, lanes = slot_words(w), table_lanes(w)
    per_row = lanes // s
    rows = -(-n // per_row)
    padded = jnp.pad(sketches, ((0, rows * per_row - n), (0, s - w)))
    return padded.reshape(rows, lanes)


def resident_table(sketches: jax.Array) -> jax.Array:
    """The sketch table an index keeps for its (n, W) master-order rows:
    blocked where the rows live on a TPU, else the rows themselves."""
    if all(d.platform == "tpu" for d in sketches.devices()):
        return block_sketches(sketches)
    return sketches


def is_blocked(table: jax.Array, w: int) -> bool:
    """Whether ``table`` is a blocked table of w-word sketches, by shape."""
    return table.shape[1] != w


def unblock_sketches(table: jax.Array, n: int, w: int) -> jax.Array:
    """The first n sketches of a resident table, (n, w): the inverse of
    :func:`block_sketches`, and an (n, W) table as it is."""
    if not is_blocked(table, w):
        return table[:n]
    s = slot_words(w)
    return table.reshape(-1, s)[:n, :w]


def row_and_slot(table: jax.Array, pos: jax.Array, w: int
                 ) -> Tuple[jax.Array, jax.Array]:
    """The table row that holds each master position of ``pos``, and the
    position's slot in that row."""
    per_row = table.shape[1] // slot_words(w)
    return pos // per_row, pos % per_row


def gather_rows(table: jax.Array, pos: jax.Array, w: int
                ) -> Tuple[jax.Array, jax.Array]:
    """The whole table row (..., L) that holds each master position of
    ``pos`` (...,), and the position's slot in it (...,)."""
    row, slot = row_and_slot(table, pos, w)
    return table[row], slot


def read_blocked(table: jax.Array, pos: jax.Array, w: int) -> jax.Array:
    """Sketches at master positions ``pos`` (...,) of a blocked table ->
    (..., w), in XLA.

    Gathers each position's whole row, then keeps its slot with a masked
    OR over the row's slots (the other slots read as zero), so no gather
    indexes below a row.  ``repro.kernels.hamming.gather_sketches`` is the
    Pallas form for a TPU.
    """
    s = slot_words(w)
    rows, slots = gather_rows(table, pos, w)
    rows = rows.reshape(pos.shape + (-1, s))
    hit = slots[..., None] == jnp.arange(rows.shape[-2], dtype=pos.dtype)
    picked = jnp.where(hit[..., None], rows, jnp.uint32(0))
    return jnp.bitwise_or.reduce(picked, axis=-2)[..., :w]


def read_sketches(table: jax.Array, pos: jax.Array, w: int) -> jax.Array:
    """Sketches at master positions ``pos`` (...,) of a resident table
    (blocked or (n, W)) -> (..., w), in XLA."""
    if is_blocked(table, w):
        return read_blocked(table, pos, w)
    return table[pos]


def pack_bits(bits: jax.Array) -> jax.Array:
    """Pack (n, d) {0,1} into (n, ceil(d/32)) uint32, bit 31 of word 0 first."""
    n, d = bits.shape
    w = sketch_words(d)
    pad = w * 32 - d
    if pad:
        bits = jnp.pad(bits, ((0, 0), (0, pad)))
    b = bits.reshape(n, w, 32).astype(jnp.uint32)
    shifts = (31 - jnp.arange(32, dtype=jnp.uint32)).astype(jnp.uint32)
    return jnp.sum(b << shifts[None, None, :], axis=-1, dtype=jnp.uint32)


@jax.jit
def make_sketches(quant: Quantizer, x: jax.Array) -> jax.Array:
    """Sketch fp vectors directly: bit i = x_i >= median_i (packed uint32)."""
    levels = quant.centroids.shape[1]
    median = quant.boundaries[:, levels // 2 - 1]  # quantile 1/2
    return pack_bits((x >= median[None, :]).astype(jnp.uint32))


@jax.jit
def sketches_from_codes(codes: jax.Array, bits: int = 4) -> jax.Array:
    """Sketch = code MSB (the shared bit); exact alias of make_sketches."""
    msb = (codes >= (1 << (bits - 1))).astype(jnp.uint32)
    return pack_bits(msb)


@jax.jit
def hamming_distance(a: jax.Array, b: jax.Array) -> jax.Array:
    """Hamming distance between packed sketches.

    a: (..., W) uint32, b: (..., W) uint32 (broadcastable) -> (...) int32.
    The Pallas kernel in ``repro.kernels.hamming`` implements the batched
    (Q, C) contract; this jnp form is the oracle and the CPU path.
    """
    x = jnp.bitwise_xor(a, b)
    return jnp.sum(lax.population_count(x).astype(jnp.int32), axis=-1)
