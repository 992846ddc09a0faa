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

from repro.kernels.hamming.kernel import (BC, BQ, ROWS_BK, ROWS_BQ,
                                          hamming_matrix_kernel,
                                          hamming_rows_kernel)
from repro.kernels.hamming.ref import hamming_matrix_ref


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
    bk = min(ROWS_BK, -(-k // 128) * 128)
    # Word-major candidates (Q, W, K): K on lanes (see kernel.py).
    qp = _pad_to(queries, ROWS_BQ, 0)[:, :, None]
    cp = _pad_to(_pad_to(jnp.swapaxes(candidates, 1, 2), ROWS_BQ, 0), bk, 2)
    out = hamming_rows_kernel(qp, cp, bk=bk)
    return out[:qn, :k]
