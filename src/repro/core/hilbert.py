"""Fast Hilbert sort for high-dimensional points, TPU-native formulation.

The paper's fast Hilbert sort [Imamura et al., SISAP 2016] is a recursive,
in-place binary partition that follows the Hilbert curve's Gray-code orthant
order one axis at a time — average O(n log n), no Hilbert indices ever
materialized.  That control-flow shape does not map onto TPU.  We keep the
*insight* (only enough curve depth to isolate small cells is needed) and
compute, per point, a **truncated Hilbert key**: the top ``key_bits`` bits of
the Hilbert index, via Skilling's transform ("Programming the Hilbert curve",
AIP Conf. Proc. 707, 2004).  Skilling's transform is O(d·b) identical bit-ops
per point — perfectly data-parallel over n points (VPU-friendly) — and the
truncated keys are sorted lexicographically (:func:`lexsort_words`).

Key layout: a key is ``W = ceil(key_bits/32)`` uint32 words, word 0 most
significant, bit 31 of word 0 the most significant bit.  The Hilbert index bit
stream interleaves the transformed coordinates MSB-level-first:
``stream[s] = bit (b-1 - s//d) of X[s % d]``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = [
    "axes_to_transpose",
    "transpose_to_axes",
    "quantize_points",
    "hilbert_keys",
    "hilbert_sort",
    "lexsort_words",
    "lex_less",
    "lex_searchsorted",
    "key_words",
]


def key_words(key_bits: int) -> int:
    """Number of uint32 words used to store a ``key_bits``-bit key."""
    return -(-key_bits // 32)


# ---------------------------------------------------------------------------
# Skilling transform
# ---------------------------------------------------------------------------


def _prefix_xor(x: jax.Array) -> jax.Array:
    """Inclusive prefix-XOR along axis 0 via Hillis-Steele doubling."""
    s = 1
    while s < x.shape[0]:
        x = x ^ jnp.concatenate([jnp.zeros_like(x[:s]), x[:-s]], axis=0)
        s <<= 1
    return x


def _level_pass(x: jax.Array, level: int, reverse: bool) -> jax.Array:
    """One level of Skilling's "inverse undo" on dims-major (d, n) coords.

    Skilling's per-level loop threads a register (X[0]) through the dims:
      i == 0:  if X[0] & Q: X[0] ^= P                     (invert register)
      i >= 1:  if X[i] & Q: X[0] ^= P                     (invert register)
               else:        t = (X[0]^X[i]) & P; X[0] ^= t; X[i] ^= t
    Every step is an involution, so ``reverse=True`` (the inverse pass used
    by :func:`transpose_to_axes`) runs the same steps backwards: dims
    d-1..1, then the i == 0 step.

    It runs as written, a ``lax.scan`` over dims carrying the register, each
    step elementwise over the n points: one read and one write of each row
    per level.  Not as prefix sums and a running max over dims: a TPU
    lowers ``cumsum``/``cummax`` as a full-width ``reduce_window``, which
    at d=384 moves ~720 GB per 65536 points (compiler cost analysis for a
    TPU v5e).
    """
    q = jnp.uint32(1 << level)
    p = jnp.uint32((1 << level) - 1)

    def first(x0):
        return jnp.where((x0 & q) != 0, x0 ^ p, x0)

    def step(x0, xi):
        invert = (xi & q) != 0
        t = (x0 ^ xi) & p
        return (jnp.where(invert, x0 ^ p, x0 ^ t),
                jnp.where(invert, xi, xi ^ t))

    x0 = x[0] if reverse else first(x[0])
    x0, body = lax.scan(step, x0, x[1:], reverse=reverse)
    if reverse:
        x0 = first(x0)
    return jnp.concatenate([x0[None], body], axis=0)


def _axes_to_transpose_t(x: jax.Array, bits: int) -> jax.Array:
    """:func:`axes_to_transpose` on dims-major (d, n) uint32 coordinates."""
    # --- Inverse undo: for Q = M .. 2. ---
    for level in range(bits - 1, 0, -1):
        x = _level_pass(x, level, reverse=False)

    # --- Gray encode: X[i] ^= X[i-1] (already-updated) == prefix-XOR. ---
    # Hillis-Steele doubling instead of ``lax.associative_scan``: when the
    # associative scan is fused with ``_level_pass`` under jit, XLA:CPU
    # miscompiles the composition (observed at d=2, bits=2, jax 0.4.37:
    # jitted keys disagree with op-by-op eval and collide).  Same O(log d)
    # depth, no scan primitive for the fuser to mangle.
    x = _prefix_xor(x)
    t = jnp.zeros(x.shape[1:], jnp.uint32)
    last = x[-1]
    for level in range(bits - 1, 0, -1):
        q = jnp.uint32(1 << level)
        t = jnp.where((last & q) != 0, t ^ jnp.uint32((1 << level) - 1), t)
    return x ^ t[None]


def axes_to_transpose(coords: jax.Array, bits: int) -> jax.Array:
    """Skilling's AxesToTranspose, vectorized over points.

    Args:
      coords: (n, d) uint32 grid coordinates, each in [0, 2**bits).
      bits: number of bits per coordinate (b).

    Returns:
      (n, d) uint32 "transpose" representation: bit ``l`` of output column
      ``i`` is Hilbert-index bit at stream position ``(bits-1-l)*d + i``.
    """
    return _axes_to_transpose_t(coords.astype(jnp.uint32).T, bits).T


def transpose_to_axes(transpose: jax.Array, bits: int) -> jax.Array:
    """Inverse of :func:`axes_to_transpose` (used by tests/oracles)."""
    x = transpose.astype(jnp.uint32).T   # dims-major (d, n)

    # Gray decode.  Forward computed t from the pre-XOR y[-1]; here we
    # only have z = y ^ t, but t's contribution to bit `level` comes solely
    # from already-reconstructed higher levels, so probe (z ^ t_sofar).
    t = jnp.zeros(x.shape[1:], jnp.uint32)
    last = x[-1]
    for level in range(bits - 1, 0, -1):
        q = jnp.uint32(1 << level)
        t = jnp.where(((last ^ t) & q) != 0, t ^ jnp.uint32((1 << level) - 1), t)
    x = x ^ t[None]
    # Invert the prefix-XOR: y[i] = x[0]^..^x[i]  =>  x[i] = y[i] ^ y[i-1].
    x = jnp.concatenate([x[:1], x[1:] ^ x[:-1]], axis=0)

    # Undo "inverse undo": same involutive level pass, run backwards
    # (dims d-1..1 then the i==0 op), levels in the opposite order.
    for level in range(1, bits):
        x = _level_pass(x, level, reverse=True)
    return x.T


# ---------------------------------------------------------------------------
# Keys
# ---------------------------------------------------------------------------


def quantize_points(
    points: jax.Array,
    bits: int,
    lo: jax.Array,
    hi: jax.Array,
) -> jax.Array:
    """Uniformly quantize fp points (n, d) into [0, 2**bits) grid coords."""
    span = jnp.maximum(hi - lo, 1e-12)
    levels = (1 << bits) - 1
    t = (points - lo) / span
    g = jnp.clip(jnp.round(t * levels), 0, levels)
    return g.astype(jnp.uint32)


def _pack_bits_to_words(bit_rows: jax.Array, key_bits: int) -> jax.Array:
    """Pack a dims-major (L*d, n) {0,1} bit matrix into (n, W) uint32,
    MSB-first."""
    w = key_words(key_bits)
    bits_mat = bit_rows[:key_bits]
    pad = w * 32 - bits_mat.shape[0]
    if pad:
        bits_mat = jnp.pad(bits_mat, ((0, pad), (0, 0)))
    bits_mat = bits_mat.reshape(w, 32, -1).astype(jnp.uint32)
    shifts = (31 - jnp.arange(32, dtype=jnp.uint32)).astype(jnp.uint32)
    words = jnp.sum(bits_mat << shifts[None, :, None], axis=1,
                    dtype=jnp.uint32)
    return words.T


KEY_CHUNK = 1 << 18


@functools.partial(jax.jit, static_argnames=("bits", "key_bits"))
def hilbert_keys(
    points: jax.Array,
    *,
    bits: int,
    key_bits: int,
    lo: jax.Array,
    hi: jax.Array,
    perm: Optional[jax.Array] = None,
    flip: Optional[jax.Array] = None,
) -> jax.Array:
    """Truncated Hilbert keys for fp points.

    Args:
      points: (n, d) float array.
      bits: grid bits per axis (curve depth).
      key_bits: number of leading Hilbert-index bits to keep.
      lo/hi: (d,) quantization bounds.
      perm: optional (d,) axis permutation (the forest's randomization).
      flip: optional (d,) bool, per-axis reflection.

    Returns:
      (n, W) uint32 packed keys, word 0 most significant.

    Keys are computed ``KEY_CHUNK`` rows at a time (``lax.map``), which
    bounds the dims-major temporaries (the (2d, rows) bit planes alone are
    3 GB for 2^20 rows at d=384).  Rows are independent, so the keys do not
    depend on the chunking.
    """
    n, d = points.shape
    if key_bits > d * bits:
        raise ValueError(f"key_bits={key_bits} exceeds d*bits={d * bits}")
    chunk = max(1, min(n, KEY_CHUNK))
    n_chunks = -(-n // chunk)
    padded = jnp.pad(points, ((0, n_chunks * chunk - n), (0, 0)))
    keys = lax.map(
        lambda rows: _keys_rows(rows, bits, key_bits, lo, hi, perm, flip),
        padded.reshape(n_chunks, chunk, d),
    )
    return keys.reshape(-1, keys.shape[-1])[:n]


def _keys_rows(points, bits, key_bits, lo, hi, perm, flip):
    # Dims-major from here on: the axis permutation is a gather of whole
    # rows, and the transform is elementwise over points.
    d = points.shape[1]
    coords = quantize_points(points, bits, lo, hi).T        # (d, n)
    if flip is not None:
        levels = jnp.uint32((1 << bits) - 1)
        coords = jnp.where(flip[:, None], levels - coords, coords)
    if perm is not None:
        coords = coords[perm]
    tr = _axes_to_transpose_t(coords, bits)
    # Interleave MSB-level-first: level b-1 of all dims, then b-2, ...
    n_levels = -(-key_bits // d)
    rows = [(tr >> jnp.uint32(bits - 1 - j)) & jnp.uint32(1)
            for j in range(n_levels)]
    return _pack_bits_to_words(jnp.concatenate(rows, axis=0), key_bits)


@jax.jit
def lexsort_words(keys: jax.Array) -> jax.Array:
    """argsort of (n, W) packed keys, lexicographic, word 0 primary.

    Least-significant word first, W stable single-key sorts in a
    ``fori_loop``: equal keys keep their index order.  One ``lax.sort``
    over all W words gives the same order, but the TPU compiler takes
    minutes for it at n ≈ 2^20 (its compile time grows with the operand
    count and with n); the loop body's two-operand sort compiles once.
    """
    n, w = keys.shape

    def body(i, order):
        word = keys[order, w - 1 - i]
        return lax.sort((word, order), num_keys=1, is_stable=True)[1]

    return lax.fori_loop(0, w, body, jnp.arange(n, dtype=jnp.int32))


def hilbert_sort(
    points: jax.Array,
    *,
    bits: int,
    key_bits: int,
    lo: jax.Array,
    hi: jax.Array,
    perm: Optional[jax.Array] = None,
    flip: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Hilbert-sort ``points``; returns (order, sorted_keys).

    ``order`` is an int32 permutation such that ``points[order]`` walks the
    (truncated) Hilbert curve; ``sorted_keys`` are the packed keys in that
    order (used to build the rank directory / "compressed Hilbert tree").

    Not jitted as a whole: called outside ``jit``, the key pass and the
    sort are separate dispatches, so every Hilbert sort of the same row
    count — the forest's trees, the master order, the k-NN graph's orders
    — shares one compiled sort.
    """
    keys = hilbert_keys(
        points, bits=bits, key_bits=key_bits, lo=lo, hi=hi, perm=perm, flip=flip
    )
    order = lexsort_words(keys)
    return order, keys[order]


# ---------------------------------------------------------------------------
# Lexicographic search over packed keys
# ---------------------------------------------------------------------------


def lex_less(a: jax.Array, b: jax.Array) -> jax.Array:
    """Lexicographic ``a < b`` over trailing word axis (word 0 primary)."""
    w = a.shape[-1]
    out = jnp.zeros(jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1]), bool)
    for i in range(w - 1, -1, -1):
        ai, bi = a[..., i], b[..., i]
        out = (ai < bi) | ((ai == bi) & out)
    return out


@jax.jit
def lex_searchsorted(sorted_keys: jax.Array, query_keys: jax.Array) -> jax.Array:
    """Vectorized left-insertion binary search on packed multi-word keys.

    Args:
      sorted_keys: (m, W) uint32, lexicographically sorted.
      query_keys: (q, W) uint32.

    Returns:
      (q,) int32 positions p with sorted[p-1] < query <= sorted[p] semantics
      (``searchsorted(..., side='left')``).
    """
    m = sorted_keys.shape[0]
    q = query_keys.shape[0]
    steps = max(1, int(np.ceil(np.log2(m + 1))))

    def body(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // 2
        mid_keys = sorted_keys[mid]  # (q, W) gather
        go_right = lex_less(mid_keys, query_keys)  # sorted[mid] < query
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right, hi, mid)
        return lo, hi

    lo = jnp.zeros((q,), jnp.int32)
    hi = jnp.full((q,), m, jnp.int32)
    lo, _ = lax.fori_loop(0, steps, body, (lo, hi))
    return lo
