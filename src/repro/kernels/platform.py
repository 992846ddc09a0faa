"""One place that decides how a Pallas kernel lowers.

Every kernel in this package goes through :func:`pallas_call`.  The choice
between the Mosaic compiler and Pallas's interpreter is made once, by the
platform the surrounding computation is *lowered for* (``lax.
platform_dependent``), not by the process's default backend and not by a
flag at each call site:

* a TPU lowering is always the compiled Mosaic kernel (``tpu_custom_call``
  in the HLO) — including an ahead-of-time compile for a described TPU
  topology from a CPU-only host;
* a CPU lowering runs the same kernel body in the interpreter, which is how
  the CPU tests check kernels against their oracles.

Production code on CPU never reaches the interpreter: ``backend="auto"``
routes CPU searches to the XLA path (``repro.index.facade.resolve_backend``).
"""

from __future__ import annotations

from jax import lax
from jax.experimental import pallas as pl

__all__ = ["pallas_call"]


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call(kernel, **kwargs)`` lowered per platform (see module
    docstring).  Takes every ``pl.pallas_call`` argument except
    ``interpret``, which is not the caller's to choose."""
    compiled = pl.pallas_call(kernel, **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)

    def call(*args):
        return lax.platform_dependent(*args, cpu=interpreted, default=compiled)

    return call
