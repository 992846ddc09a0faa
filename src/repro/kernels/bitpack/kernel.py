"""Pallas TPU kernel: bit-plane packing for Hilbert keys / sketches.

Key generation ends by packing a (n, K) {0,1} bit matrix into (n, K/32)
uint32 words (MSB-first).  The jnp path materializes an (n, W, 32) uint32
intermediate (32× write amplification before the reduce); the kernel keeps
a (BN, 32·BW) bit tile in VMEM and emits the packed (BN, BW) tile directly
— pure VPU shifts+adds, HBM traffic = bits-in (1 B/bit as u8) + words-out.

Grid (n/BN, W/BW); weights the popcount/qdist kernels read downstream.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import pallas_call

BN = 256
BW = 4  # words per tile -> 128 bit-columns, one lane register


def _pack_kernel(bits_ref, out_ref):
    bits = bits_ref[...].astype(jnp.uint32)       # (BN, BW*32)
    bn, total = bits.shape
    w = total // 32
    b3 = bits.reshape(bn, w, 32)
    shifts = (31 - jax.lax.broadcasted_iota(jnp.uint32, (1, 1, 32), 2))
    out_ref[...] = jnp.sum(b3 << shifts, axis=-1, dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("bn", "bw"))
def pack_bits_kernel(
    bits: jax.Array,          # (N, K) uint8/bool in {0,1}; K % (32*bw) == 0
    *,
    bn: int = BN,
    bw: int = BW,
) -> jax.Array:
    n, k = bits.shape
    w = k // 32
    grid = (n // bn, w // bw)
    return pallas_call(
        _pack_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bn, bw * 32), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bn, bw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, w), jnp.uint32),
    )(bits.astype(jnp.uint8))
