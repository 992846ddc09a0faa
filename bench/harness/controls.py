"""The control: the reference, one precision step down, in the program's
place.

A run with the control installed goes through the same drivers, engine,
window and checks as a benchmark run, with ``HilbertIndex.build``
returning a :class:`ControlIndex`, whose search and graph are the plain
reference computed one step below the precision the configuration states
(its ``"control"`` key):

* ``{"bits": 2}`` — Task 1 ranks by distance to 4-bit codes; the control
  ranks by exact distance to the corpus at 2 bits per coordinate (the
  step that halves the bits, as int8 -> int4);
* ``{"precision": "high"}`` — Task 2's final selection is exact float32;
  the control computes it with the three-pass bfloat16 product.

``correct`` has to come out false for it (``bench/control.py`` on the
chip, ``tests/bench`` on the CPU).
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, Iterator, Optional

import jax.numpy as jnp

from bench.harness import reference

SAMPLE_ROWS = 1 << 18


class ControlIndex:
    """Stands in for ``repro.index.HilbertIndex``: the attributes the
    drivers and ``MutableHilbertIndex.from_index`` use, with the reference
    behind ``search`` and ``knn_graph``."""

    def __init__(self, points, config, *, bits: Optional[int] = None,
                 precision: str = "highest"):
        self.points, self.config, self.precision = points, config, precision
        self.corpus = points if bits is None else reference.quantised(
            points, points[:SAMPLE_ROWS], bits=bits)

    @property
    def n_points(self) -> int:
        return self.points.shape[0]

    @property
    def dim(self) -> int:
        return self.points.shape[1]

    def search(self, queries, params, **_):
        return reference.exact_topk(jnp.asarray(queries), self.corpus,
                                    params.k, precision=self.precision)

    def knn_graph(self, params, **_):
        ids, d2 = reference.exact_topk(self.points, self.corpus,
                                       params.k + 1,
                                       precision=self.precision)
        rows = jnp.arange(self.n_points, dtype=jnp.int32)[:, None]
        # Drop each row's own id (first, at distance 0 up to rounding).
        keep = jnp.argsort(ids == rows, axis=1, stable=True)[:, :params.k]
        return (jnp.take_along_axis(ids, keep, axis=1),
                jnp.take_along_axis(d2, keep, axis=1))


@contextlib.contextmanager
def installed(control: Dict[str, Any]) -> Iterator[None]:
    """Make ``HilbertIndex.build`` return a :class:`ControlIndex` with the
    configuration's ``control`` settings inside the body."""
    from repro.index import HilbertIndex

    original = HilbertIndex.__dict__["build"]
    HilbertIndex.build = classmethod(
        lambda cls, points, config=None: ControlIndex(points, config,
                                                      **control))
    try:
        yield
    finally:
        HilbertIndex.build = original
