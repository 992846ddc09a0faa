"""The plain reference: exact squared-L2 k-nearest-neighbour search.

Straightforward ``jax.numpy`` on the device, blocked over corpus rows so a
2^20-row corpus fits beside nothing else.  Distances are
``|q|^2 - 2 q.x + |x|^2``; the cross term is float32 at
``Precision.HIGHEST`` for the reference.  The controls lower it:
``"high"`` is the three-pass bfloat16 product a TPU runs for
``Precision.HIGH`` (hi*hi + hi*lo + lo*hi of each operand's bfloat16
split), ``"bf16"`` one bfloat16 pass (:func:`cross`); or they quantise
the corpus (:func:`quantised`).  It imports nothing of the program under test.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

CORPUS_BLOCK = 1 << 15
QUERY_BLOCK = 4096
PAIR_BLOCK = 1 << 12

PRECISIONS = ("highest", "high", "bf16")


def cross(q: jax.Array, x: jax.Array, precision: str) -> jax.Array:
    """``q @ x.T`` in float32 at ``precision`` (one of ``PRECISIONS``).

    On a TPU ``"high"`` is ``Precision.HIGH``, the chip's own three-pass
    product.  Elsewhere, and for ``"bf16"``, the passes are spelled out:
    each operand is split into bfloat16 parts with ``lax.reduce_precision``
    (which, unlike a round trip through ``astype``, no compiler may drop as
    excess precision) and the parts are multiplied exactly in float32.
    """
    def dot(a, b, p=lax.Precision.HIGHEST):
        return jnp.matmul(a, b.T, precision=p)

    if precision == "highest":
        return dot(q, x)
    if precision == "high" and jax.default_backend() == "tpu":
        return dot(q, x, lax.Precision.HIGH)

    def split(a):
        hi = lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
        lo = lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)
        return hi, lo

    (qh, ql), (xh, xl) = split(q), split(x)
    if precision == "bf16":
        return dot(qh, xh)
    if precision == "high":
        return dot(qh, xh) + (dot(qh, xl) + dot(ql, xh))
    raise ValueError(f"precision must be one of {PRECISIONS}")


@functools.partial(jax.jit, static_argnames=("k", "precision"))
def _block_topk(q, x, x_valid, base, best_i, best_d, *, k, precision):
    qq = jnp.sum(q * q, axis=1)[:, None]
    xx = jnp.sum(x * x, axis=1)[None, :]
    cross_ = cross(q, x, precision)
    d2 = jnp.maximum(qq - 2.0 * cross_ + xx, 0.0)
    d2 = jnp.where(x_valid[None, :], d2, jnp.inf)
    neg, idx = lax.top_k(-d2, min(k, x.shape[0]))
    ids = jnp.concatenate([best_i, (idx + base).astype(jnp.int32)], axis=1)
    dist = jnp.concatenate([best_d, -neg], axis=1)
    neg, pick = lax.top_k(-dist, k)
    return jnp.take_along_axis(ids, pick, axis=1), -neg


def exact_topk(queries: jax.Array, corpus: jax.Array, k: int, *,
               valid: Optional[jax.Array] = None,
               precision: str = "highest") -> Tuple[jax.Array, jax.Array]:
    """Exact top-``k`` rows of ``corpus`` for each query.

    Returns ``(ids (Q, k) int32, d2 (Q, k) float32)`` ascending; rows with
    ``valid[i] == False`` never appear (their slots read id -1 / +inf when
    fewer than ``k`` valid rows exist).
    """
    n = corpus.shape[0]
    if valid is None:
        valid = jnp.ones((n,), bool)
    out_i, out_d = [], []
    for s in range(0, queries.shape[0], QUERY_BLOCK):
        q = queries[s:s + QUERY_BLOCK]
        best_i = jnp.full((q.shape[0], k), -1, jnp.int32)
        best_d = jnp.full((q.shape[0], k), jnp.inf, jnp.float32)
        for b in range(0, n, CORPUS_BLOCK):
            best_i, best_d = _block_topk(
                q, corpus[b:b + CORPUS_BLOCK], valid[b:b + CORPUS_BLOCK],
                jnp.int32(b), best_i, best_d, k=k, precision=precision)
        out_i.append(jnp.where(jnp.isfinite(best_d), best_i, -1))
        out_d.append(best_d)
    return jnp.concatenate(out_i), jnp.concatenate(out_d)


@jax.jit
def _pair_block(q, corpus, ids):
    x = corpus[jnp.clip(ids, 0, corpus.shape[0] - 1)]
    diff = q[:, None, :] - x
    return jnp.sum(diff * diff, axis=-1)


def pair_d2(queries: jax.Array, corpus: jax.Array, ids) -> np.ndarray:
    """Float32 ``|q_r - corpus[ids[r, j]]|^2`` elementwise, as float64 host
    array (slots with id < 0 read NaN)."""
    ids = jnp.asarray(ids, jnp.int32)
    out = []
    for s in range(0, queries.shape[0], PAIR_BLOCK):
        out.append(np.asarray(_pair_block(queries[s:s + PAIR_BLOCK], corpus,
                                          ids[s:s + PAIR_BLOCK])))
    d2 = np.concatenate(out).astype(np.float64)
    return np.where(np.asarray(ids) < 0, np.nan, d2)


@functools.partial(jax.jit, static_argnames=("bits",))
def quantised(corpus: jax.Array, sample: jax.Array, *, bits: int) -> jax.Array:
    """``corpus`` with every coordinate replaced by the centre of its cell
    among ``2**bits`` per-dimension quantile cells fitted on ``sample``:
    the corpus at ``bits`` bits per coordinate, for the controls."""
    levels = 1 << bits
    edges = jnp.quantile(sample, jnp.arange(1, levels) / levels, axis=0)
    centres = jnp.quantile(sample, (jnp.arange(levels) + 0.5) / levels,
                           axis=0)                        # (levels, d)
    code = jnp.sum(corpus[:, :, None] >= edges.T[None], axis=-1)
    return jnp.take_along_axis(centres.T, code.T, axis=1).T
