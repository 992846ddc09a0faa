"""One process per chip, and one fixed compile cache.

* A process on an accelerator holds the chip, so the out-of-process
  compactor's child must be given the CPU whatever the parent runs on.
* Every entry point shares one persistent compilation cache: the directory
  ``JAX_COMPILATION_CACHE_DIR`` names, or else one fixed in-checkout path.
"""

import os

import jax
import pytest

from repro.launch import compile_cache
from repro.serve import compactor


@pytest.mark.parametrize("parent_env", [None, "tpu"])
def test_compactor_child_runs_on_cpu_when_parent_is_on_tpu(monkeypatch,
                                                           parent_env):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if parent_env is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", parent_env)
    env = compactor._child_env()
    assert env["JAX_PLATFORMS"] == "cpu"
    assert (f"--xla_force_host_platform_device_count={jax.device_count()}"
            in env["XLA_FLAGS"])


def test_compile_cache_honours_env_var(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_one_fixed_in_checkout_path(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = compile_cache.enable_compile_cache()
        second = compile_cache.enable_compile_cache()
        assert first == second == compile_cache.DEFAULT_DIR
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = compile_cache.CHECKOUT_ROOT
    assert first == os.path.join(root, ".jax_cache")
    assert os.path.exists(os.path.join(root, "pyproject.toml"))
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
