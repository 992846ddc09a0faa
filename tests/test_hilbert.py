"""Hilbert sort correctness: oracle + structural properties.

The defining property of a Hilbert curve on the full b-bit grid: sorting all
grid cells by Hilbert index yields a Hamiltonian path where consecutive cells
differ by exactly 1 in exactly one axis.  We assert that for d in {2, 3} and
several depths — a complete, oracle-free characterization of the curve.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hilbert


def _full_grid(d, bits):
    pts = np.array(list(itertools.product(range(1 << bits), repeat=d)), np.float64)
    return pts


@pytest.mark.parametrize("d,bits", [(2, 1), (2, 2), (2, 3), (3, 1), (3, 2)])
def test_full_grid_is_hamiltonian_path(d, bits):
    pts = _full_grid(d, bits)
    lo = jnp.zeros((d,))
    hi = jnp.full((d,), float((1 << bits) - 1))
    order, _ = hilbert.hilbert_sort(
        jnp.asarray(pts), bits=bits, key_bits=d * bits, lo=lo, hi=hi
    )
    walk = pts[np.asarray(order)]
    steps = np.abs(np.diff(walk, axis=0))
    # each consecutive pair differs by exactly 1 in exactly one coordinate
    assert np.all(steps.sum(axis=1) == 1), "not a unit-step walk"
    assert np.all(steps.max(axis=1) == 1)
    # visits every cell exactly once
    assert len(np.unique(np.asarray(order))) == len(pts)


@pytest.mark.parametrize("d,bits,key_bits", [(2, 2, 4), (3, 2, 6), (16, 2, 32),
                                             (48, 4, 192)])
def test_hilbert_keys_jit_matches_eager(d, bits, key_bits):
    """jitted keys == op-by-op keys.

    Regression test for an XLA:CPU miscompile: ``lax.associative_scan``
    (the Gray-encode prefix-XOR) fused with ``_level_pass`` produced
    colliding, non-Hamiltonian keys at d=2, bits=2 under jit only — the
    seed-era ``test_full_grid_is_hamiltonian_path[2-2]`` failure.  Fixed by
    the Hillis-Steele ``_prefix_xor`` formulation.
    """
    rng = np.random.default_rng(7)
    pts = jnp.asarray(rng.normal(size=(257, d)).astype(np.float32))
    lo = jnp.full((d,), -4.0)
    hi = jnp.full((d,), 4.0)
    with jax.disable_jit():
        ref = np.asarray(
            hilbert.hilbert_keys(pts, bits=bits, key_bits=key_bits, lo=lo, hi=hi)
        )
    got = np.asarray(
        hilbert.hilbert_keys(pts, bits=bits, key_bits=key_bits, lo=lo, hi=hi)
    )
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("d,bits", [(2, 4), (5, 3), (16, 2), (48, 4)])
def test_transpose_roundtrip(d, bits):
    rng = np.random.default_rng(0)
    coords = rng.integers(0, 1 << bits, size=(257, d)).astype(np.uint32)
    tr = hilbert.axes_to_transpose(jnp.asarray(coords), bits)
    back = hilbert.transpose_to_axes(tr, bits)
    np.testing.assert_array_equal(np.asarray(back), coords)


def test_truncated_key_prefix_consistency():
    """Sorting by a longer key refines (never contradicts) a shorter key."""
    rng = np.random.default_rng(1)
    pts = jnp.asarray(rng.normal(size=(512, 8)).astype(np.float32))
    lo = jnp.full((8,), -4.0)
    hi = jnp.full((8,), 4.0)
    k_short = hilbert.hilbert_keys(pts, bits=6, key_bits=32, lo=lo, hi=hi)
    k_long = hilbert.hilbert_keys(pts, bits=6, key_bits=48, lo=lo, hi=hi)
    # first word identical
    np.testing.assert_array_equal(np.asarray(k_short[:, 0]), np.asarray(k_long[:, 0]))


def test_lex_searchsorted_matches_numpy_bigint():
    rng = np.random.default_rng(2)
    m, q, w = 1000, 128, 3
    sorted_np = rng.integers(0, 2**32, size=(m, w), dtype=np.uint32)
    as_int = [tuple(int(x) for x in row) for row in sorted_np]
    as_int.sort()
    sorted_np = np.array(as_int, dtype=np.uint32)
    queries = rng.integers(0, 2**32, size=(q, w), dtype=np.uint32)
    # include exact hits
    queries[:10] = sorted_np[rng.integers(0, m, 10)]
    got = np.asarray(
        hilbert.lex_searchsorted(jnp.asarray(sorted_np), jnp.asarray(queries))
    )
    ref = np.searchsorted(
        np.array([int.from_bytes(r.tobytes(), "little") for r in sorted_np[:, ::-1]]),
        np.array([int.from_bytes(r.tobytes(), "little") for r in queries[:, ::-1]]),
        side="left",
    )
    np.testing.assert_array_equal(got, ref)


def test_locality_better_than_random():
    """Hilbert-order neighbors are closer in L2 than random pairs (on average)."""
    rng = np.random.default_rng(3)
    pts = rng.normal(size=(4096, 16)).astype(np.float32)
    lo = jnp.full((16,), float(pts.min()))
    hi = jnp.full((16,), float(pts.max()))
    order, _ = hilbert.hilbert_sort(
        jnp.asarray(pts), bits=8, key_bits=128, lo=lo, hi=hi
    )
    walk = pts[np.asarray(order)]
    adj = np.linalg.norm(np.diff(walk, axis=0), axis=1).mean()
    perm = rng.permutation(4096)
    rand = np.linalg.norm(np.diff(pts[perm], axis=0), axis=1).mean()
    # In d=16 the NN-distance floor is ~2.7 and random pairs ~5.6; a single
    # Hilbert order lands in between (~4.2) — partial locality is exactly why
    # the paper uses a *forest* of orders.  Assert a clear locality signal.
    assert adj < 0.8 * rand, (adj, rand)


def test_perm_and_flip_change_order_but_not_set():
    rng = np.random.default_rng(4)
    pts = jnp.asarray(rng.normal(size=(512, 12)).astype(np.float32))
    lo = jnp.full((12,), -4.0)
    hi = jnp.full((12,), 4.0)
    o1, _ = hilbert.hilbert_sort(pts, bits=6, key_bits=64, lo=lo, hi=hi)
    perm = jnp.asarray(rng.permutation(12).astype(np.int32))
    flip = jnp.asarray(rng.integers(0, 2, 12).astype(bool))
    o2, _ = hilbert.hilbert_sort(
        pts, bits=6, key_bits=64, lo=lo, hi=hi, perm=perm, flip=flip
    )
    assert not np.array_equal(np.asarray(o1), np.asarray(o2))
    assert sorted(np.asarray(o2).tolist()) == list(range(512))


def test_hilbert_keys_row_chunks_match_one_pass(monkeypatch):
    """Past KEY_CHUNK rows the keys are computed in row blocks (to bound
    device scratch); the blocks, ragged tail included, give the keys of
    one pass."""
    rng = np.random.default_rng(5)
    pts = jnp.asarray(rng.normal(size=(2500, 24)).astype(np.float32))
    lo, hi = jnp.full((24,), -4.0), jnp.full((24,), 4.0)
    perm = jnp.asarray(rng.permutation(24).astype(np.int32))
    flip = jnp.asarray(rng.integers(0, 2, 24).astype(bool))
    kw = dict(bits=4, key_bits=80, lo=lo, hi=hi, perm=perm, flip=flip)
    one_pass = hilbert.hilbert_keys(pts, **kw)
    monkeypatch.setattr(hilbert, "KEY_CHUNK", 1000)
    hilbert.hilbert_keys.clear_cache()
    chunked = hilbert.hilbert_keys(pts, **kw)
    hilbert.hilbert_keys.clear_cache()
    np.testing.assert_array_equal(np.asarray(chunked), np.asarray(one_pass))


@pytest.mark.parametrize("n", [1, 7, 300])
def test_lexsort_words_matches_numpy_lexsort(n):
    """The LSD loop of stable single-word sorts is the lexicographic order,
    equal keys in index order (np.lexsort is stable)."""
    rng = np.random.default_rng(n)
    keys = rng.integers(0, 4, size=(n, 5)).astype(np.uint32)  # many ties
    got = np.asarray(hilbert.lexsort_words(jnp.asarray(keys)))
    ref = np.lexsort(tuple(keys[:, i] for i in range(4, -1, -1)))
    np.testing.assert_array_equal(got, ref)
