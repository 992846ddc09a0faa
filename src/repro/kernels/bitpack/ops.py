"""Jit'd wrapper: pads to tile multiples, dispatches kernel/oracle."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.bitpack.kernel import BN, BW, pack_bits_kernel
from repro.kernels.bitpack.ref import pack_bits_ref


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def pack_bits(
    bits: jax.Array,
    *,
    use_kernel: bool = False,
) -> jax.Array:
    """Pack a (N, K) {0,1} matrix into (N, ceil(K/32)) uint32 (MSB-first)."""
    n, k = bits.shape
    kp = -(-k // (32 * BW)) * (32 * BW)
    np_ = -(-n // BN) * BN
    if not use_kernel:
        padded = jnp.pad(bits, ((0, 0), (0, kp - k)))
        return pack_bits_ref(padded)[:, : -(-k // 32)]
    padded = jnp.pad(bits, ((0, np_ - n), (0, kp - k)))
    out = pack_bits_kernel(padded)
    return out[:n, : -(-k // 32)]
