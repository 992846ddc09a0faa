"""Synthetic ANN datasets with exact ground truth.

Stand-ins for PUBMED23 (23M x 384) / GOOAQ (3M x 384) at container scale.
Embedding-like data: clustered unit-norm vectors (text-embedding geometry),
plus an isotropic Gaussian control.  Ground truth is exact brute force,
chunked to bound memory.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

__all__ = [
    "clustered_embeddings",
    "lowrank_embeddings",
    "lowrank_dataset_with_queries",
    "dataset_with_queries",
    "gaussian",
    "exact_knn",
    "exact_knn_graph",
    "recall_at_k",
]


def clustered_embeddings(
    n: int,
    d: int,
    n_clusters: int = 64,
    seed: int = 0,
    noise: float = 0.25,
    decay: float = 0.35,
) -> np.ndarray:
    """Unit-norm clustered vectors with a decaying covariance spectrum.

    Real sentence-embedding sets (PUBMED23/GOOAQ are MiniLM-style vectors)
    concentrate variance in a few tens of principal directions; the power-law
    per-dim scale (``decay``) reproduces that.  Space-filling-curve locality
    depends strongly on this anisotropy — the isotropic control lives in
    :func:`gaussian` (and is the documented worst case for the method).
    """
    rng = np.random.default_rng(seed)
    scale = ((1.0 + np.arange(d)) ** -decay).astype(np.float32)
    centers = rng.normal(size=(n_clusters, d)).astype(np.float32) * scale
    assign = rng.integers(0, n_clusters, n)
    x = centers[assign] + noise * rng.normal(size=(n, d)).astype(np.float32) * scale
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    return x.astype(np.float32)


def lowrank_embeddings(
    n: int,
    d: int,
    n_clusters: int = 64,
    r: int = 16,
    noise: float = 0.9,
    seed: int = 0,
) -> np.ndarray:
    """Clusters living on low-dimensional local manifolds (intrinsic dim r≪d).

    The realistic proxy for MiniLM-style corpora (PUBMED23/GOOAQ): ambient
    d=384 but local intrinsic dimensionality ~10–30, which gives (a) smooth
    local density with *meaningful distance gaps* between the 30th and 300th
    neighbor (rankable by a 4-bit quantizer) and (b) strong per-dim
    correlation between true neighbors (what space-filling-curve locality
    exploits).  Isotropic full-rank cluster noise has neither — in d=384 all
    within-cluster distances concentrate and recall@30 becomes unresolvable
    for ANY quantized index; see EXPERIMENTS.md §Datasets.

    Resulting stats at n=20k: NN cos ≈ 0.82 (1st) / 0.61 (30th), random-pair
    cos ≈ 0.0 — matching published MiniLM corpus statistics.
    """
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(n_clusters, d)).astype(np.float32)
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    assign = rng.integers(0, n_clusters, n)
    u = rng.normal(size=(n_clusters, d, r)).astype(np.float32)
    u /= np.linalg.norm(u, axis=1, keepdims=True)
    spec = ((1.0 + np.arange(r)) ** -0.5).astype(np.float32)
    z = rng.normal(size=(n, r)).astype(np.float32) * spec
    # One (rows, r) @ (r, d) product per cluster: a per-row gather of the
    # (d, r) bases would move n*d*r floats (26 GB at n=2^20).
    x = np.empty((n, d), np.float32)
    for c in range(n_clusters):
        rows = np.flatnonzero(assign == c)
        x[rows] = centers[c] + noise * (z[rows] @ u[c].T)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    return x


def lowrank_dataset_with_queries(
    n: int,
    q: int,
    d: int,
    n_clusters: int = 64,
    r: int = 16,
    noise: float = 0.9,
    seed: int = 0,
) -> Tuple[np.ndarray, np.ndarray]:
    """(data, held-out queries), one distribution — the challenge's regime."""
    allpts = lowrank_embeddings(
        n + q, d, n_clusters=n_clusters, r=r, noise=noise, seed=seed
    )
    perm = np.random.default_rng(seed + 0x9E3779B9).permutation(n + q)
    allpts = allpts[perm]
    return allpts[:n], allpts[n:]


def gaussian(n: int, d: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32)


def dataset_with_queries(
    n: int,
    q: int,
    d: int,
    n_clusters: int = 64,
    seed: int = 0,
    noise: float = 0.25,
    decay: float = 0.35,
) -> Tuple[np.ndarray, np.ndarray]:
    """(data, held-out queries) drawn from ONE distribution.

    SISAP challenge queries come from the corpus distribution (PUBMED23
    queries are paper abstracts like the indexed ones); drawing queries from
    *re-generated* cluster centers is an out-of-distribution regime the
    challenge does not test and space-filling-curve locality does not claim.
    """
    allpts = clustered_embeddings(
        n + q, d, n_clusters=n_clusters, seed=seed, noise=noise, decay=decay
    )
    rng = np.random.default_rng(seed + 0x9E3779B9)
    perm = rng.permutation(n + q)
    allpts = allpts[perm]
    return allpts[:n], allpts[n:]


def exact_knn(
    data: np.ndarray, queries: np.ndarray, k: int, chunk: int = 1024
) -> Tuple[np.ndarray, np.ndarray]:
    """Brute-force k-NN (squared L2). Returns (ids (Q,k), dists (Q,k))."""
    data_sq = (data * data).sum(1)
    ids = np.empty((len(queries), k), np.int32)
    dists = np.empty((len(queries), k), np.float32)
    for s in range(0, len(queries), chunk):
        q = queries[s : s + chunk]
        d2 = data_sq[None, :] - 2.0 * (q @ data.T) + (q * q).sum(1)[:, None]
        part = np.argpartition(d2, k - 1, axis=1)[:, :k]
        pd = np.take_along_axis(d2, part, axis=1)
        srt = np.argsort(pd, axis=1)
        ids[s : s + chunk] = np.take_along_axis(part, srt, axis=1)
        dists[s : s + chunk] = np.take_along_axis(pd, srt, axis=1)
    return ids, dists


def exact_knn_graph(data: np.ndarray, k: int, chunk: int = 1024) -> np.ndarray:
    """Exact k-NN graph ids (self excluded)."""
    ids, _ = exact_knn(data, data, k + 1, chunk=chunk)
    out = np.empty((len(data), k), np.int32)
    for i in range(len(data)):
        row = ids[i]
        row = row[row != i][:k]
        out[i] = row
    return out


def recall_at_k(pred_ids: np.ndarray, true_ids: np.ndarray) -> float:
    """Mean |pred ∩ true| / k (the challenge's recall metric)."""
    k = true_ids.shape[1]
    hits = 0
    for p, t in zip(pred_ids, true_ids):
        hits += len(set(p[:k].tolist()) & set(t.tolist()))
    return hits / (len(true_ids) * k)
