"""Algorithm 2 jitted stages: approximate k-NN graph construction (Task 2).

.. note::
   The public entry point is ``repro.index.HilbertIndex.knn_graph(params)``,
   which **reuses the already-fit quantizer/codes/sketches** of a built
   index instead of re-fitting.  This module holds the pure pipeline
   (:func:`knn_graph_from_sketches`) the facade consumes, plus a
   deprecation shim (:func:`build_knn_graph`) for one release.

Every point is a query, so no tree/binary-search is needed: a point's
stage-1 candidates are its ±k1/2 rank-neighbors in each Hilbert order, and
an order can be discarded as soon as its candidates are merged — memory is
constant in the number of orders (paper §4.1: "memory consumption remains
constant, with only the computation time increasing").

As in :mod:`repro.core.search` we merge each order's candidates into a
running sketch-filtered top-k2 (associative, exact) instead of materializing
all n·k1 candidates (which would be ~92 GB at challenge scale).

Unlike Algorithm 1's serving path, graph construction never touches the
quantized codes (the final re-rank is exact fp32 against the stored
points), so it is unaffected by the packed-resident code layout the search
path moved to — only the shared sketches flow in from the index.
"""

from __future__ import annotations

import functools
import warnings
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import hilbert, quantize, search, sketch
from repro.core.types import ForestConfig, GraphParams, QuantizerConfig

__all__ = ["build_knn_graph", "knn_graph_from_sketches"]

_INF = jnp.int32(2**30)


def order_and_rank(points, lo, hi, perm, flip, *, bits, key_bits):
    """One Hilbert order + its inverse rank (pure stage; not jitted, so it
    shares the compiled sort of the index build)."""
    order, _ = hilbert.hilbert_sort(
        points, bits=bits, key_bits=key_bits, lo=lo, hi=hi, perm=perm, flip=flip
    )
    return order, _inverse_permutation(order)


@jax.jit
def _inverse_permutation(order):
    n = order.shape[0]
    return jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))


# Rows per pass of merge_order: its (rows, k1, W) candidate-sketch gather
# and (rows, k2 + k1) sort need ~14 GB at 2^20 rows in one pass.
MERGE_CHUNK = 1 << 16


@functools.partial(jax.jit, static_argnames=("k1", "k2"))
def merge_order(best_id, best_dist, order, rank, sketches, *, k1, k2):
    """Merge one Hilbert order's rank-window candidates into the top-k2.

    Rows are merged in blocks of at most ``MERGE_CHUNK`` (``lax.map``);
    rows are independent, so the result does not depend on the blocking.
    """
    n = order.shape[0]
    chunk = max(1, min(n, MERGE_CHUNK))
    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    rows = jnp.minimum(jnp.arange(n + pad, dtype=jnp.int32), n - 1)

    def block(x, fill):
        x = jnp.pad(x, ((0, pad), (0, 0)), constant_values=fill)
        return x.reshape(n_chunks, chunk, x.shape[-1])

    ids, dist = lax.map(
        lambda a: _merge_rows(*a, order, rank, sketches, k1=k1, k2=k2),
        (rows.reshape(n_chunks, chunk), block(best_id, -1),
         block(best_dist, _INF)),
    )
    return ids.reshape(-1, k2)[:n], dist.reshape(-1, k2)[:n]


def _merge_rows(rows, best_id, best_dist, order, rank, sketches, *, k1, k2):
    """One block of rows, its steps under the named scopes
    ``merge_order.candidates``, ``merge_order.hamming`` and
    ``merge_order.merge``."""
    n = order.shape[0]
    half = k1 // 2
    with jax.named_scope("merge_order.candidates"):
        # ±half window around each point's rank, self excluded by distance
        # mask.
        deltas = jnp.concatenate(
            [jnp.arange(-half, 0, dtype=jnp.int32),
             jnp.arange(1, k1 - half + 1, dtype=jnp.int32)]
        )  # k1 offsets, 0 excluded
        pos = rank[rows][:, None] + deltas[None, :]
        pos = jnp.clip(pos, 0, n - 1)
        cand = order[pos]  # (rows, k1) ids
    with jax.named_scope("merge_order.hamming"):
        hd = sketch.hamming_distance(sketches[rows][:, None, :],
                                     sketches[cand])
        self_mask = cand == rows[:, None]
        hd = jnp.where(self_mask, _INF, hd)
    with jax.named_scope("merge_order.merge"):
        return search._merge_topk_dedup(best_id, best_dist, cand, hd, k2)


@functools.partial(jax.jit, static_argnames=("k",))
def final_select_chunk(points, best_id_chunk, row_start, *, k):
    """Exact fp32 distances to the k2 survivors; top-k (paper: top-15)."""
    cand_vecs = points[best_id_chunk]  # (C, k2, d)
    rows = row_start + jnp.arange(best_id_chunk.shape[0], dtype=jnp.int32)
    diff = points[rows][:, None, :] - cand_vecs
    d2 = jnp.sum(diff * diff, axis=-1)
    d2 = jnp.where(best_id_chunk < 0, jnp.inf, d2)
    d2 = jnp.where(best_id_chunk == rows[:, None], jnp.inf, d2)
    neg, idx = lax.top_k(-d2, k)
    return jnp.take_along_axis(best_id_chunk, idx, axis=1), -neg


def knn_graph_from_sketches(
    points: jax.Array,
    sketches: jax.Array,
    params: GraphParams,
    *,
    bits: int,
    key_bits: int,
    lo: jax.Array,
    hi: jax.Array,
    chunk: int = 1 << 14,
) -> Tuple[jax.Array, jax.Array]:
    """Full Algorithm-2 pipeline over pre-computed sketches (pure function).

    ``sketches`` must be in point-id order (row i = point i).  Both the
    facade (which reuses the index's fitted sketches) and the legacy shim
    (which fits its own) funnel through here, so results are bit-identical.
    """
    n, d = points.shape
    rng = np.random.default_rng(params.seed)
    best_id = jnp.full((n, params.k2), -1, jnp.int32)
    best_dist = jnp.full((n, params.k2), _INF, jnp.int32)
    for _ in range(params.n_orders):
        perm = jnp.asarray(rng.permutation(d).astype(np.int32))
        flip = jnp.asarray(rng.integers(0, 2, d).astype(bool))
        order, rank = order_and_rank(
            points, lo, hi, perm, flip, bits=bits, key_bits=key_bits
        )
        best_id, best_dist = merge_order(
            best_id, best_dist, order, rank, sketches, k1=params.k1, k2=params.k2
        )
    # Final exact selection, chunked over points to bound the (N, k2, d)
    # gather transient (~3 GB per 2^14 rows at k2=60, d=384).
    ids_out, d_out = [], []
    for s in range(0, n, chunk):
        ids_c, d_c = final_select_chunk(
            points, best_id[s : s + chunk], s, k=params.k
        )
        ids_out.append(ids_c)
        d_out.append(d_c)
    return jnp.concatenate(ids_out), jnp.concatenate(d_out)


def build_knn_graph(
    points: jax.Array,
    params: GraphParams,
    quant_cfg: QuantizerConfig = QuantizerConfig(),
    forest_cfg: ForestConfig = ForestConfig(),
    chunk: int = 1 << 14,
) -> Tuple[jax.Array, jax.Array]:
    """DEPRECATED: use ``repro.index.HilbertIndex.build(...).knn_graph(...)``.

    Re-fits a quantizer/sketches from scratch on every call; the facade
    reuses the ones already fitted at index build time.
    """
    warnings.warn(
        "repro.core.knn_graph.build_knn_graph is deprecated; use "
        "repro.index.HilbertIndex.knn_graph(params), which reuses the "
        "index's fitted quantizer/sketches",
        DeprecationWarning,
        stacklevel=2,
    )
    quant = quantize.fit(points, bits=quant_cfg.bits, sample_limit=quant_cfg.sample_limit)
    codes = quantize.encode(quant, points)
    sketches = sketch.sketches_from_codes(codes, bits=quant_cfg.bits)
    lo = jnp.min(points, axis=0)
    hi = jnp.max(points, axis=0)
    return knn_graph_from_sketches(
        points, sketches, params,
        bits=forest_cfg.bits, key_bits=forest_cfg.key_bits, lo=lo, hi=hi,
        chunk=chunk,
    )
