"""Jit'd public wrappers for the qdist kernels: pad, permute, dispatch."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.qdist.kernel import (
    BC,
    BQ,
    WIN_BC,
    WIN_BQ,
    packed_dim_order,
    qdist_packed_kernel,
    qdist_packed_windows_kernel,
    qdist_u8_kernel,
)
from repro.kernels.qdist.ref import (
    qdist_packed_ref,
    qdist_packed_windows_ref,
    qdist_u8_ref,
)


def _pad_axis(x: jax.Array, m: int, axis: int) -> jax.Array:
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def qdist(
    queries: jax.Array,
    codes: jax.Array,
    centroids: jax.Array,
    *,
    use_kernel: bool = False,
) -> jax.Array:
    """Asymmetric squared-L2: fp32 queries vs uint8-coded database rows.

    Args:
      queries: (Q, D) float32.
      codes: (C, D) uint8 in [0, L).
      centroids: (D, L) float32 per-dim reconstruction table.

    Returns: (Q, C) float32 squared distances.
    """
    if not use_kernel:
        return qdist_u8_ref(queries, codes, centroids)
    qn, d = queries.shape
    cn = codes.shape[0]
    # Pad D to a lane multiple with zero query/centroid columns (code 0 then
    # reconstructs to 0.0 — zero contribution to the distance).
    dp = -(-d // 128) * 128
    q = jnp.pad(queries, ((0, (-qn) % BQ), (0, dp - d)))
    c = jnp.pad(codes, ((0, (-cn) % BC), (0, dp - d)))
    cent = jnp.pad(centroids, ((0, dp - d), (0, 0)))
    out = qdist_u8_kernel(q, c, cent, levels=centroids.shape[1])
    return out[:qn, :cn]


@functools.partial(jax.jit, static_argnames=("d", "use_kernel"))
def qdist_from_packed(
    queries: jax.Array,
    packed: jax.Array,
    centroids: jax.Array,
    *,
    d: int,
    use_kernel: bool = False,
) -> jax.Array:
    """Packed-nibble codes variant — 0.5 B/dim HBM traffic on TPU.

    Args:
      queries: (Q, D) float32.
      packed: (C, ceil(D/8)) uint32, nibble-packed 4-bit codes.
      centroids: (D, 16) float32.
      d: original dimensionality.
    """
    if not use_kernel:
        return qdist_packed_ref(queries, packed, centroids, d=d)
    qn = queries.shape[0]
    cn, w = packed.shape
    # Pad packed width so 8·W is a lane multiple; nibble 0 + zero centroid
    # columns contribute nothing.
    wp = -(-w // 16) * 16
    dp = 8 * wp
    q = jnp.pad(queries, ((0, (-qn) % BQ), (0, dp - d)))
    p = jnp.pad(packed, ((0, (-cn) % BC), (0, wp - w)))
    cent = jnp.pad(centroids, ((0, dp - d), (0, 0)))
    order = jnp.asarray(packed_dim_order(dp))
    out = qdist_packed_kernel(
        q[:, order], p, cent[order], levels=centroids.shape[1]
    )
    return out[:qn, :cn]


@functools.partial(jax.jit, static_argnames=("d", "use_kernel"))
def qdist_windows_from_packed(
    queries: jax.Array,
    packed_windows: jax.Array,
    centroids: jax.Array,
    *,
    d: int,
    use_kernel: bool = False,
) -> jax.Array:
    """Per-query packed candidate sets — the fused stage-2 serving shape.

    Args:
      queries: (Q, D) float32.
      packed_windows: (Q, C, ceil(D/8)) uint32 — each query's own candidate
        codes (the ±h master-order windows), nibble-packed.
      centroids: (D, 16) float32.
      d: original dimensionality.

    Returns: (Q, C) float32 squared distances.
    """
    if not use_kernel:
        return qdist_packed_windows_ref(queries, packed_windows, centroids, d=d)
    qn = queries.shape[0]
    _, cn, w = packed_windows.shape
    levels = centroids.shape[1]
    # Pad the packed width to a sublane multiple (nibble 0 against zero
    # centroid rows and zero query dims contributes nothing) and the
    # candidates to whole tiles (all-zero rows whose finite distances are
    # sliced away below).  Candidates go word-major (Q, W, C); query dims
    # and centroids are split by nibble: dim 8·w + s -> [s, w].
    wp = -(-w // 8) * 8
    dp = 8 * wp
    bc = min(WIN_BC, -(-cn // 128) * 128)
    q = jnp.pad(queries, ((0, (-qn) % WIN_BQ), (0, dp - d)))
    q = jnp.swapaxes(q.reshape(-1, wp, 8), 1, 2)[..., None]
    p = jnp.pad(packed_windows, ((0, (-qn) % WIN_BQ), (0, (-cn) % bc),
                                 (0, wp - w)))
    p = jnp.swapaxes(p, 1, 2)
    cent = jnp.pad(centroids, ((0, dp - d), (0, 0)))
    cent = jnp.transpose(cent.reshape(wp, 8, levels), (1, 2, 0))
    cent = cent.reshape(8 * levels, wp, 1)
    out = qdist_packed_windows_kernel(q, p, cent, levels=levels, bc=bc)
    return out[:qn, :cn]
