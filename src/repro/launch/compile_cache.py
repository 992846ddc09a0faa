"""The persistent compilation cache every entry point shares.

Entry points (``chip_smoke.py``, ``python -m repro.launch.serve``,
``python -m benchmarks.run``, the compactor child) call
:func:`enable_compile_cache` once at start-up; importing the library never
does, so tests compile without it.

* If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and this
  sets nothing.
* Otherwise the cache goes to ``<checkout>/.jax_cache`` (git-ignored).  The
  path is fixed: it is part of each entry's key, so a path built from a
  temp name, a PID or the time would never hit.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
