"""Pallas kernel validation: interpret-mode vs pure-jnp oracles.

Per the brief: sweep shapes/dtypes for each kernel and assert_allclose
against the ref.py oracle.  Interpret mode executes the kernel body in
Python on CPU — same program the Mosaic compiler would lower on TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st  # guarded dev-only import

from repro.core import quantize
from repro.kernels.hamming import hamming_matrix, hamming_matrix_ref
from repro.kernels.qdist import (
    qdist,
    qdist_from_packed,
    qdist_windows_from_packed,
)
from repro.kernels.qdist.ref import qdist_u8_ref

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# hamming
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "q,c,w",
    [
        (1, 1, 1),
        (7, 129, 12),      # non-multiples force padding
        (128, 128, 12),    # exact single tile
        (130, 257, 16),    # multi-tile + ragged edge
        (64, 512, 3),
    ],
)
def test_hamming_kernel_matches_ref(q, c, w):
    a = jnp.asarray(RNG.integers(0, 2**32, size=(q, w), dtype=np.uint32))
    b = jnp.asarray(RNG.integers(0, 2**32, size=(c, w), dtype=np.uint32))
    got = hamming_matrix(a, b, use_kernel=True)
    ref = hamming_matrix_ref(a, b)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_hamming_known_values():
    a = jnp.asarray(np.array([[0x0, 0xFFFFFFFF]], np.uint32))
    b = jnp.asarray(np.array([[0x0, 0xFFFFFFFF], [0xF, 0xFFFFFFFF], [0x0, 0x0]], np.uint32))
    got = np.asarray(hamming_matrix(a, b, use_kernel=True))
    np.testing.assert_array_equal(got, [[0, 4, 32]])


@settings(max_examples=25, deadline=None)
@given(
    q=st.integers(1, 40),
    c=st.integers(1, 160),
    w=st.integers(1, 20),
    seed=st.integers(0, 2**31 - 1),
)
def test_hamming_kernel_property(q, c, w, seed):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.integers(0, 2**32, size=(q, w), dtype=np.uint32))
    b = jnp.asarray(rng.integers(0, 2**32, size=(c, w), dtype=np.uint32))
    got = np.asarray(hamming_matrix(a, b, use_kernel=True))
    ref = np.asarray(hamming_matrix_ref(a, b))
    np.testing.assert_array_equal(got, ref)
    # metric properties: symmetry on identical args, range
    assert got.min() >= 0 and got.max() <= 32 * w


# ---------------------------------------------------------------------------
# qdist
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "q,c,d",
    [
        (1, 1, 8),
        (5, 200, 48),
        (128, 128, 384),   # paper shape, exact tiles
        (130, 300, 384),
        (16, 64, 100),     # d not multiple of 8/128
    ],
)
def test_qdist_u8_kernel_matches_ref(q, c, d):
    data = RNG.normal(size=(c, d)).astype(np.float32)
    queries = jnp.asarray(RNG.normal(size=(q, d)).astype(np.float32))
    quant = quantize.fit(jnp.asarray(data), bits=4)
    codes = quantize.encode(quant, jnp.asarray(data))
    got = qdist(queries, codes, quant.centroids, use_kernel=True)
    ref = qdist_u8_ref(queries, codes, quant.centroids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("q,c,d", [(4, 100, 48), (128, 128, 384), (9, 257, 128)])
def test_qdist_packed_kernel_matches_ref(q, c, d):
    data = RNG.normal(size=(c, d)).astype(np.float32)
    queries = jnp.asarray(RNG.normal(size=(q, d)).astype(np.float32))
    quant = quantize.fit(jnp.asarray(data), bits=4)
    codes = quantize.encode(quant, jnp.asarray(data))
    packed = quantize.pack_codes(codes)
    got = qdist_from_packed(
        queries, packed, quant.centroids, d=d, use_kernel=True
    )
    ref = qdist_u8_ref(queries, codes, quant.centroids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("q,c,d", [(1, 1, 8), (3, 100, 48), (9, 130, 384), (5, 260, 128)])
def test_qdist_windows_kernel_matches_ref(q, c, d):
    """Per-query candidate sets (Q, C, W) — the fused stage-2 shape."""
    data = RNG.normal(size=(q * c, d)).astype(np.float32)
    queries = jnp.asarray(RNG.normal(size=(q, d)).astype(np.float32))
    quant = quantize.fit(jnp.asarray(data), bits=4)
    codes = quantize.encode(quant, jnp.asarray(data))
    windows = jax.vmap(quantize.pack_codes)(codes.reshape(q, c, d))
    got = qdist_windows_from_packed(
        queries, windows, quant.centroids, d=d, use_kernel=True
    )
    per_query_ref = [
        np.asarray(
            qdist_u8_ref(queries[i : i + 1], codes.reshape(q, c, d)[i], quant.centroids)
        )[0]
        for i in range(q)
    ]
    np.testing.assert_allclose(
        np.asarray(got), np.stack(per_query_ref), rtol=1e-5, atol=1e-5
    )


def test_qdist_zero_distance_to_self_centroids():
    """A query equal to a reconstructed vector has (near-)zero distance."""
    d = 64
    data = RNG.normal(size=(32, d)).astype(np.float32)
    quant = quantize.fit(jnp.asarray(data), bits=4)
    codes = quantize.encode(quant, jnp.asarray(data))
    recon = quantize.decode(quant, codes)
    got = np.asarray(
        qdist(recon, codes, quant.centroids, use_kernel=True)
    )
    np.testing.assert_allclose(np.diag(got), 0.0, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(
    q=st.integers(1, 16),
    c=st.integers(1, 64),
    d=st.sampled_from([8, 16, 48, 96]),
    seed=st.integers(0, 2**31 - 1),
)
def test_qdist_property_nonneg_and_exact(q, c, d, seed):
    rng = np.random.default_rng(seed)
    data = rng.normal(size=(c, d)).astype(np.float32)
    queries = jnp.asarray(rng.normal(size=(q, d)).astype(np.float32))
    quant = quantize.fit(jnp.asarray(data), bits=4)
    codes = quantize.encode(quant, jnp.asarray(data))
    got = np.asarray(
        qdist(queries, codes, quant.centroids, use_kernel=True)
    )
    ref = np.asarray(qdist_u8_ref(queries, codes, quant.centroids))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)
    assert (got > -1e-4).all()


# ---------------------------------------------------------------------------
# bitpack
# ---------------------------------------------------------------------------

from repro.kernels.bitpack import pack_bits, pack_bits_ref  # noqa: E402


@pytest.mark.parametrize("n,k", [(1, 32), (7, 100), (256, 128), (300, 448), (64, 31)])
def test_bitpack_kernel_matches_ref(n, k):
    bits = jnp.asarray(RNG.integers(0, 2, size=(n, k), dtype=np.uint8))
    got = pack_bits(bits, use_kernel=True)
    ref = pack_bits(bits, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_bitpack_msb_first():
    bits = jnp.zeros((1, 32), jnp.uint8).at[0, 0].set(1)
    out = np.asarray(pack_bits(bits, use_kernel=True))
    assert out[0, 0] == 1 << 31


@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 80), k=st.integers(1, 200), seed=st.integers(0, 2**31 - 1))
def test_bitpack_property(n, k, seed):
    rng = np.random.default_rng(seed)
    bits = jnp.asarray(rng.integers(0, 2, size=(n, k), dtype=np.uint8))
    got = np.asarray(pack_bits(bits, use_kernel=True))
    ref = np.asarray(pack_bits_ref(jnp.asarray(np.pad(
        np.asarray(bits), ((0, 0), (0, (-k) % 32))))))[:, : -(-k // 32)]
    np.testing.assert_array_equal(got, ref)
