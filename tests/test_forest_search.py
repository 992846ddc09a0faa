"""Forest / Algorithm-1 search / Algorithm-2 graph behaviour tests.

Validates the paper's claims at container scale, at the paper's
dimensionality (d=384, MiniLM-style geometry — see
``ann_datasets.lowrank_embeddings`` for why intrinsic dimension matters):
  * Task-1-style search hits recall@30 > 0.7 with a modest forest.
  * Task-2-style graph construction hits recall@15 > 0.8.
  * Recall is monotone in the number of trees/orders (the paper's
    "using more trees improves recall").
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import knn_graph, quantize, search, sketch
from repro.core.types import ForestConfig, GraphParams, QuantizerConfig, SearchParams
from repro.data import ann_datasets

N, D, Q = 12000, 384, 200


@pytest.fixture(scope="module")
def dataset():
    # Held-out queries from the SAME distribution (the challenge's regime:
    # PUBMED23 queries are abstracts like the indexed ones).
    data, queries = ann_datasets.lowrank_dataset_with_queries(
        N, Q, D, n_clusters=48, seed=0
    )
    gt, _ = ann_datasets.exact_knn(data, queries, 30)
    return data, queries, gt


@pytest.fixture(scope="module")
def index(dataset):
    data, _, _ = dataset
    cfg = ForestConfig(n_trees=16, bits=4, key_bits=448, leaf_size=32, seed=0)
    return search.build_index(jnp.asarray(data), cfg), cfg


def test_task1_recall_band(dataset, index):
    data, queries, gt = dataset
    idx, cfg = index
    params = SearchParams(k1=48, k2=384, h=2, k=30)
    ids, dists = search.search(idx, jnp.asarray(queries), params, cfg)
    rec = ann_datasets.recall_at_k(np.asarray(ids), gt)
    assert rec > 0.7, f"recall@30={rec}"
    # distances are sorted ascending
    d = np.asarray(dists)
    assert np.all(np.diff(d, axis=1) >= -1e-5)


def test_no_duplicate_results(dataset, index):
    data, queries, gt = dataset
    idx, cfg = index
    params = SearchParams(k1=48, k2=384, h=2, k=30)
    ids, _ = search.search(idx, jnp.asarray(queries), params, cfg)
    ids = np.asarray(ids)
    for row in ids:
        assert len(set(row.tolist())) == len(row)


def test_recall_monotone_in_trees(dataset, index):
    """Paper §2: "Using more trees improves recall"."""
    data, queries, gt = dataset
    idx16, cfg16 = index
    recalls = []
    for n_trees in (2, 6):
        cfg = ForestConfig(n_trees=n_trees, bits=4, key_bits=448, leaf_size=32)
        idx = search.build_index(jnp.asarray(data), cfg)
        params = SearchParams(k1=48, k2=384, h=2, k=30)
        ids, _ = search.search(idx, jnp.asarray(queries), params, cfg)
        recalls.append(ann_datasets.recall_at_k(np.asarray(ids), gt))
    ids, _ = search.search(
        idx16, jnp.asarray(queries), SearchParams(k1=48, k2=384, h=2, k=30), cfg16
    )
    recalls.append(ann_datasets.recall_at_k(np.asarray(ids), gt))
    assert recalls[0] < recalls[-1]
    assert recalls[-1] == max(recalls)


def test_task2_graph_recall_band():
    data = ann_datasets.lowrank_embeddings(8000, D, n_clusters=32, seed=3)
    gt = ann_datasets.exact_knn_graph(data, 15)
    params = GraphParams(n_orders=20, k1=48, k2=96, k=15, seed=0)
    ids, dists = knn_graph.build_knn_graph(
        jnp.asarray(data), params, forest_cfg=ForestConfig(bits=4, key_bits=448)
    )
    rec = ann_datasets.recall_at_k(np.asarray(ids), gt)
    assert rec > 0.8, f"recall@15={rec}"
    ids = np.asarray(ids)
    # no self edges, no duplicates
    assert not np.any(ids == np.arange(len(data))[:, None])
    for row in ids[:500]:
        assert len(set(row.tolist())) == len(row)


def test_memory_report_shared_bit(index):
    idx, _ = index
    rep = idx.memory_report()
    # combined < sketches + codes (the shared-MSB saving), all positive
    assert rep["combined_stage2_bytes"] < rep["sketch_bytes"] + rep["quantized_bytes"]
    assert rep["forest_bytes"] > 0


def test_quantizer_roundtrip_and_shared_msb():
    data = ann_datasets.gaussian(5000, 24, seed=1)
    quant = quantize.fit(jnp.asarray(data), bits=4)
    codes = quantize.encode(quant, jnp.asarray(data))
    recon = quantize.decode(quant, codes)
    # reconstruction error bounded by cell widths
    err = np.abs(np.asarray(recon) - data).mean()
    assert err < 0.2, err
    # MSB == median bit
    sk_codes = np.asarray(sketch.sketches_from_codes(codes))
    sk_direct = np.asarray(sketch.make_sketches(quant, jnp.asarray(data)))
    mismatch = (sk_codes != sk_direct).mean()
    assert mismatch < 1e-3  # boundary ties only


def test_knn_merge_order_row_chunks_match_one_pass(monkeypatch):
    """Past MERGE_CHUNK rows an order's candidates merge in row blocks (to
    bound device scratch); the result, ragged tail included, is the one-pass
    merge's."""
    rng = np.random.default_rng(6)
    n, w, k1, k2 = 2500, 3, 16, 12
    sk = jnp.asarray(rng.integers(0, 2**32, (n, w), dtype=np.uint32))
    order = jnp.asarray(rng.permutation(n).astype(np.int32))
    rank = jnp.zeros((n,), jnp.int32).at[order].set(jnp.arange(n))
    best_id = jnp.asarray(rng.integers(-1, n, (n, k2)).astype(np.int32))
    best_d = jnp.asarray(rng.integers(0, 90, (n, k2)).astype(np.int32))
    one_pass = knn_graph.merge_order(best_id, best_d, order, rank, sk,
                                     k1=k1, k2=k2)
    monkeypatch.setattr(knn_graph, "MERGE_CHUNK", 1000)
    knn_graph.merge_order.clear_cache()
    chunked = knn_graph.merge_order(best_id, best_d, order, rank, sk,
                                    k1=k1, k2=k2)
    knn_graph.merge_order.clear_cache()
    for a, b in zip(chunked, one_pass):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_lowrank_embeddings_match_per_row_reference():
    """The generator's per-cluster products equal the per-row formula
    centers[a] + noise * U[a] @ z, up to f32 summation order over r."""
    n, d, c, r, noise = 3000, 48, 8, 16, 0.9
    got = ann_datasets.lowrank_embeddings(n, d, n_clusters=c, r=r,
                                          noise=noise, seed=3)
    rng = np.random.default_rng(3)
    centers = rng.normal(size=(c, d)).astype(np.float32)
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    assign = rng.integers(0, c, n)
    u = rng.normal(size=(c, d, r)).astype(np.float32)
    u /= np.linalg.norm(u, axis=1, keepdims=True)
    spec = ((1.0 + np.arange(r)) ** -0.5).astype(np.float32)
    z = rng.normal(size=(n, r)).astype(np.float32) * spec
    ref = np.stack([centers[a] + noise * (u[a] @ zi)
                    for a, zi in zip(assign, z)])
    ref /= np.linalg.norm(ref, axis=1, keepdims=True)
    np.testing.assert_allclose(got, ref, rtol=0,
                               atol=4 * r * np.finfo(np.float32).eps)
