"""Open-loop arrival schedules, fixed by the seed.

Every seed gets the same work in another order: the same number of
operations of each kind and the same multiset of gaps between arrivals
(the quantiles of an exponential distribution at the mix's rate, so the
arrivals are Poisson-like), permuted by the seed.  A run then differs
from another seed's run only in order, not in load.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def open_loop(seed: int, *, rate: float, seconds: float,
              shares: Dict[str, float]) -> Tuple[np.ndarray, np.ndarray]:
    """(due (N,) seconds from the window's start, kinds (N,) str).

    ``N = round(rate * seconds)``; kind ``k`` gets ``round(shares[k] * N)``
    operations, the first kind taking the rounding remainder.
    """
    if rate <= 0 or seconds <= 0:
        raise ValueError("rate and seconds must be > 0")
    if abs(sum(shares.values()) - 1.0) > 1e-9:
        raise ValueError(f"shares must sum to 1, got {shares}")
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(seed)
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u) / rate)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    names = list(shares)
    counts = [int(round(shares[k] * n)) for k in names[1:]]
    counts.insert(0, n - sum(counts))
    kinds = np.repeat(np.array(names), counts)
    return due, rng.permutation(kinds)


def lateness_ms(due: np.ndarray, sent: np.ndarray) -> Dict[str, float]:
    """How late the generator sent, in ms: median, p95 and max of
    ``sent - due`` (both on the window's clock, in seconds)."""
    late = 1000.0 * (np.asarray(sent) - np.asarray(due))
    return {"p50": float(np.percentile(late, 50)),
            "p95": float(np.percentile(late, 95)),
            "max": float(late.max())}
