"""Seeded corpora for the benchmark, made on the device.

The recipe is the repository's low-rank embedding stand-in for the SISAP
corpora (``repro.data.ann_datasets.lowrank_embeddings``), kept here so the
yardstick does not move when the program does: ``n_clusters`` unit-norm
centres, each with its own ``rank``-dimensional basis of unit columns, rows
``centre + noise * basis @ z`` with ``z ~ N(0, diag((1 + i) ** -1))`` and
then normalised to unit length.  Rows are independent, so data, held-out
queries and rows to insert are consecutive slices of one draw.

Everything is one jitted call on the device (the host generator took 7-11
s at 2^20 rows).  The values differ from the host generator's; the
distribution is the same.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

ROW_BLOCK = 1 << 14


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative integer seed (more than 32 bits)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


@functools.partial(jax.jit, static_argnames=("rows", "d", "n_clusters",
                                             "rank", "noise"))
def _lowrank(key, *, rows, d, n_clusters, rank, noise):
    k_c, k_u, k_a, k_z = jax.random.split(key, 4)
    centers = jax.random.normal(k_c, (n_clusters, d), jnp.float32)
    centers /= jnp.linalg.norm(centers, axis=1, keepdims=True)
    basis = jax.random.normal(k_u, (n_clusters, d, rank), jnp.float32)
    basis /= jnp.linalg.norm(basis, axis=1, keepdims=True)
    spec = (1.0 + jnp.arange(rank, dtype=jnp.float32)) ** -0.5
    n_blocks = -(-rows // ROW_BLOCK)
    assign = jax.random.randint(k_a, (n_blocks, ROW_BLOCK), 0, n_clusters)
    z = jax.random.normal(k_z, (n_blocks, ROW_BLOCK, rank), jnp.float32)
    z = z * spec

    def block(args):
        a, zb = args
        x = centers[a] + noise * jnp.einsum(
            "bdr,br->bd", basis[a], zb, precision=lax.Precision.HIGHEST)
        return x / jnp.linalg.norm(x, axis=1, keepdims=True)

    x = lax.map(block, (assign, z))
    return x.reshape(n_blocks * ROW_BLOCK, d)[:rows]


def lowrank(seed: int, rows: int, d: int, *, n_clusters: int = 64,
            rank: int = 16, noise: float = 0.9) -> jax.Array:
    """(rows, d) float32 unit-norm rows on the default device."""
    return _lowrank(seed_key(seed), rows=rows, d=d, n_clusters=n_clusters,
                    rank=rank, noise=float(noise))


def corpus(seed: int, cfg: dict, extra_rows: int):
    """(data (n, d), extra (extra_rows, d)) for configuration ``cfg``.

    ``extra`` holds the held-out queries and rows to insert, drawn from the
    same distribution as the data.
    """
    gen = cfg["data"]
    n, d = cfg["rows"], cfg["dim"]
    x = lowrank(seed, n + extra_rows, d, n_clusters=gen["n_clusters"],
                rank=gen["rank"], noise=gen["noise"])
    return x[:n], x[n:]
