"""Hilbert forest: multiple Hilbert trees under randomized axis orders.

A "tree" on TPU is an implicit structure: the Hilbert-sorted **order** (an
int32 permutation) plus a **rank directory** — every ``leaf_size``-th sorted
key.  Locating a query's position is a vectorized lexicographic binary search
over the directory, the exact analogue of the paper's compressed Hilbert tree
(subtrees of ~100 points truncated to leaves; 76 MB vs 400 MB per tree).

All functions here are pure jitted stages; the public entry point that
composes them is :class:`repro.index.HilbertIndex`.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hilbert
from repro.core.types import ForestConfig

__all__ = ["HilbertForest", "build_forest", "tree_candidates"]


class HilbertForest(NamedTuple):
    """Stacked per-tree state (T trees over n points in d dims)."""

    perms: jax.Array  # (T, d) int32 — randomized axis orders
    flips: jax.Array  # (T, d) bool  — randomized reflections
    orders: jax.Array  # (T, n) int32 — point ids in per-tree Hilbert order
    directories: jax.Array  # (T, n_dir, W) uint32 — sampled sorted keys
    lo: jax.Array  # (d,) quantization bounds
    hi: jax.Array  # (d,)

    @property
    def n_trees(self) -> int:
        return self.orders.shape[0]

    @property
    def n_points(self) -> int:
        return self.orders.shape[1]

    def memory_bytes(self) -> int:
        """In-RAM index footprint (the paper's 16 GB budget accounting)."""
        return sum(
            np.prod(a.shape) * a.dtype.itemsize
            for a in (self.perms, self.flips, self.orders, self.directories)
        )


def forest_randomization(cfg: ForestConfig, d: int) -> Tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(cfg.seed)
    perms = np.stack([rng.permutation(d) for _ in range(cfg.n_trees)]).astype(np.int32)
    flips = rng.integers(0, 2, size=(cfg.n_trees, d)).astype(bool)
    return perms, flips


def _build_tree(points, lo, hi, perm, flip, *, bits, key_bits, leaf_size):
    order, sorted_keys = hilbert.hilbert_sort(
        points, bits=bits, key_bits=key_bits, lo=lo, hi=hi, perm=perm, flip=flip
    )
    directory = sorted_keys[::leaf_size]
    return order, directory


def build_forest(points: jax.Array, cfg: ForestConfig) -> HilbertForest:
    """Build ``cfg.n_trees`` Hilbert trees (streamed; one key array live)."""
    n, d = points.shape
    lo = jnp.min(points, axis=0)
    hi = jnp.max(points, axis=0)
    perms_np, flips_np = forest_randomization(cfg, d)
    orders, dirs = [], []
    for t in range(cfg.n_trees):
        order, directory = _build_tree(
            points,
            lo,
            hi,
            jnp.asarray(perms_np[t]),
            jnp.asarray(flips_np[t]),
            bits=cfg.bits,
            key_bits=cfg.key_bits,
            leaf_size=cfg.leaf_size,
        )
        orders.append(order)
        dirs.append(directory)
    return HilbertForest(
        perms=jnp.asarray(perms_np),
        flips=jnp.asarray(flips_np),
        orders=jnp.stack(orders),
        directories=jnp.stack(dirs),
        lo=lo,
        hi=hi,
    )


@functools.partial(jax.jit, static_argnames=("bits", "key_bits", "leaf_size", "k1"))
def tree_candidates(
    queries: jax.Array,
    order: jax.Array,
    directory: jax.Array,
    lo: jax.Array,
    hi: jax.Array,
    perm: jax.Array,
    flip: jax.Array,
    *,
    bits: int,
    key_bits: int,
    leaf_size: int,
    k1: int,
) -> jax.Array:
    """Per-tree stage-1: locate each query in Hilbert order, take k1 around.

    Returns (Q, k1) int32 point ids (the paper's "extract k1 candidates near
    q's position").  Window edges clip; duplicates are handled downstream.
    """
    n = order.shape[0]
    qkeys = hilbert.hilbert_keys(
        queries, bits=bits, key_bits=key_bits, lo=lo, hi=hi, perm=perm, flip=flip
    )
    j = hilbert.lex_searchsorted(directory, qkeys)  # (Q,) in [0, n_dir]
    # directory[j-1] <= q < directory[j]  =>  true rank in ((j-1)·leaf, j·leaf];
    # center the window on the interval midpoint to avoid a +leaf/2 bias.
    rank = jnp.clip(j * leaf_size - leaf_size // 2, 0, n - 1)
    start = jnp.clip(rank - k1 // 2, 0, max(n - k1, 0))
    pos = start[:, None] + jnp.arange(k1, dtype=jnp.int32)[None, :]
    pos = jnp.clip(pos, 0, n - 1)
    return order[pos]
