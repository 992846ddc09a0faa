"""Kernel↔pipeline integration: search with use_kernels=True is identical."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import search
from repro.core.types import ForestConfig, SearchParams
from repro.data import ann_datasets
from repro.kernels.hamming import hamming_rows

RNG = np.random.default_rng(0)

# Kernel and XLA paths may accumulate float32 distances in different
# orders, so "identical" is pinned to an explicit tolerance instead of
# exact equality: distances agree within DIST_RTOL/DIST_ATOL, and ids may
# differ ONLY at positions where the reference distances tie within
# TIE_ATOL (either order of a tie is a correct top-k).
DIST_RTOL = 1e-5
DIST_ATOL = 1e-6
TIE_ATOL = 1e-4


@pytest.mark.parametrize("q,k,w", [(1, 4, 3), (7, 33, 12), (130, 16, 14)])
def test_hamming_rows_kernel_matches_oracle(q, k, w):
    # integer popcounts have no accumulation-order freedom: exact equality
    a = jnp.asarray(RNG.integers(0, 2**32, (q, w), dtype=np.uint32))
    c = jnp.asarray(RNG.integers(0, 2**32, (q, k, w), dtype=np.uint32))
    got = hamming_rows(a, c, use_kernel=True)
    ref = hamming_rows(a, c, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def _assert_ids_equal_up_to_distance_ties(ids_ref, ids_got, d_ref):
    """Mismatched id positions must sit inside a run of tied distances."""
    ids_ref, ids_got = np.asarray(ids_ref), np.asarray(ids_got)
    d_ref = np.asarray(d_ref)
    mismatch = ids_ref != ids_got
    if not mismatch.any():
        return
    for r, c in zip(*np.nonzero(mismatch)):
        tied = np.isclose(d_ref[r], d_ref[r, c], atol=TIE_ATOL)
        tied_ids = set(ids_ref[r, tied].tolist())
        assert ids_got[r, c] in tied_ids, (
            f"row {r} col {c}: kernel id {ids_got[r, c]} is not among the "
            f"reference ids tied at distance {d_ref[r, c]} "
            f"(ref id {ids_ref[r, c]}, tie set {sorted(tied_ids)})"
        )


def test_search_with_kernels_is_identical():
    data, queries = ann_datasets.lowrank_dataset_with_queries(
        1500, 32, 64, n_clusters=8, r=4, seed=0)
    cfg = ForestConfig(n_trees=4, bits=4, key_bits=64, leaf_size=16, seed=0)
    idx = search.build_index(jnp.asarray(data), cfg)
    params = SearchParams(k1=16, k2=64, h=1, k=8)
    ids0, d0 = search.search(idx, jnp.asarray(queries), params, cfg)
    ids1, d1 = search.search(idx, jnp.asarray(queries), params, cfg,
                             use_kernels=True)
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1),
                               rtol=DIST_RTOL, atol=DIST_ATOL)
    _assert_ids_equal_up_to_distance_ties(ids0, ids1, d0)
