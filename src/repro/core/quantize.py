"""4-bit quantile quantization with the paper's shared sketch bit.

The paper compresses 23M fp32 vectors (36 GB) to 4-bit codes, and shares one
bit between the code and the 384-bit sketch, for a combined 4.5 GB.  The
sharing pins the construction: the code's MSB must *be* the sketch bit, i.e.
the per-dimension median threshold.  We therefore fit a per-dimension
16-level **quantile** grid (cell boundaries at quantiles k/16), so that
``code >= 8  <=>  x >= median``.

Queries are never quantized (paper §3.1): final distances are asymmetric —
fp32 query against dequantized (centroid) database vectors.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "Quantizer",
    "fit",
    "encode",
    "decode",
    "adc_distance",
    "adc_distance_packed",
    "pack_codes",
    "unpack_codes",
]


class Quantizer(NamedTuple):
    """Per-dim quantile grid.

    boundaries: (d, L-1) float32 — interior cell boundaries (quantiles k/L).
    centroids: (d, L) float32 — per-cell reconstruction values.
    """

    boundaries: jax.Array
    centroids: jax.Array

    @property
    def bits(self) -> int:
        return int(np.log2(self.centroids.shape[1]))


def fit(data: jax.Array, bits: int = 4, sample_limit: int = 262144) -> Quantizer:
    """Fit per-dimension quantile boundaries/centroids on (a sample of) data.

    ``data`` may be a host (numpy) array: only the sample then goes to the
    device, which is how the sharded build avoids staging a whole corpus
    on one device.
    """
    n = data.shape[0]
    if n > sample_limit:
        idx = np.random.default_rng(0).choice(n, sample_limit, replace=False)
        data = data[idx]
    levels = 1 << bits
    qs_b = jnp.arange(1, levels) / levels
    qs_c = (jnp.arange(levels) + 0.5) / levels
    boundaries = jnp.quantile(data, qs_b, axis=0).T.astype(jnp.float32)  # (d, L-1)
    centroids = jnp.quantile(data, qs_c, axis=0).T.astype(jnp.float32)  # (d, L)
    return Quantizer(boundaries, centroids)


@jax.jit
def encode(quant: Quantizer, x: jax.Array) -> jax.Array:
    """Quantize (n, d) floats to (n, d) uint8 codes in [0, 2**bits).

    ``code = #{boundaries < x}`` — a handful of vectorized compares instead of
    a per-row searchsorted (bits=4 -> 15 compares; VPU-trivial).
    """
    # (n, d, L-1) broadcast compare, summed over cells.
    code = jnp.sum(
        x[:, :, None] >= quant.boundaries[None, :, :], axis=-1, dtype=jnp.int32
    )
    return code.astype(jnp.uint8)


@jax.jit
def decode(quant: Quantizer, codes: jax.Array) -> jax.Array:
    """Reconstruct (n, d) float32 from uint8 codes via centroid lookup."""
    return jax.vmap(
        lambda c: jnp.take_along_axis(
            quant.centroids, c[:, None].astype(jnp.int32), axis=1
        )[:, 0]
    )(codes)


@jax.jit
def adc_distance(quant: Quantizer, queries: jax.Array, codes: jax.Array) -> jax.Array:
    """Asymmetric squared-L2: fp32 queries (q, d) vs codes (q, c, d).

    Dequantizes codes to centroids and computes ``sum((q - r)^2)`` — the
    MXU-friendly TPU formulation (vs the CPU per-dim LUT gather).  The Pallas
    kernel in ``repro.kernels.qdist`` implements the same contract.
    """
    recon = jax.vmap(jax.vmap(
        lambda c: jnp.take_along_axis(quant.centroids, c[:, None].astype(jnp.int32), axis=1)[:, 0]
    ))(codes)  # (q, c, d)
    diff = queries[:, None, :] - recon
    return jnp.sum(diff * diff, axis=-1)


def pack_codes(codes: jax.Array) -> jax.Array:
    """Pack (n, d) 4-bit codes into (n, ceil(d/8)) uint32 words.

    This is the **resident** representation: the paper budgets 23M x 384 x
    4 bit = 4.4 GB (MSB shared with the sketch), and :class:`HilbertIndex`
    stores ``codes_master`` in exactly this layout — half the RAM and HBM
    traffic of unpacked uint8.  The qdist Pallas kernel consumes the packed
    words directly on TPU; the XLA path unpacks candidate windows on the fly
    (:func:`adc_distance_packed`), which is lossless and therefore
    bit-identical to computing on unpacked codes.
    """
    n, d = codes.shape
    pad = (-d) % 8
    if pad:
        codes = jnp.pad(codes, ((0, 0), (0, pad)))
    c = codes.reshape(n, -1, 8).astype(jnp.uint32)
    shifts = jnp.arange(8, dtype=jnp.uint32) * 4
    return jnp.sum(c << shifts[None, None, :], axis=-1, dtype=jnp.uint32)


def unpack_codes(packed: jax.Array, d: int) -> jax.Array:
    """Inverse of :func:`pack_codes` (lossless; works on any leading shape).

    ``packed`` is (..., W) uint32; returns (..., d) uint8.
    """
    w = packed.shape[-1]
    shifts = jnp.arange(8, dtype=jnp.uint32) * 4
    c = (packed[..., None] >> shifts) & jnp.uint32(0xF)
    return c.reshape(*packed.shape[:-1], w * 8)[..., :d].astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("d",))
def adc_distance_packed(
    quant: Quantizer, queries: jax.Array, packed: jax.Array, *, d: int
) -> jax.Array:
    """:func:`adc_distance` on nibble-packed candidate codes (q, c, W).

    Unpacks to uint8 and reuses :func:`adc_distance`, so the result is
    **bit-identical** to the unpacked path (pack/unpack is lossless).  The
    TPU serving path instead feeds the packed words straight to the Pallas
    kernel (``repro.kernels.qdist.qdist_windows_from_packed``), trading bit
    identity for the 0.5 B/dim HBM roofline.
    """
    return adc_distance(quant, queries, unpack_codes(packed, d))
