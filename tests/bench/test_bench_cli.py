"""The command refuses to run anywhere but on a TPU, and without the
program beside it, and prints no result either way."""

import os
import shutil
import subprocess
import sys

from bench.harness import spec


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "pubmed23.task1_batch", "--seed", str(2 ** 33 + 1), "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_refuses_a_cpu():
    p = _run(spec.ROOT)
    assert p.returncode == 1, p.stderr
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    for path in spec.load_spec()["paths"]:
        shutil.copytree(os.path.join(spec.ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(spec.SPEC_FILE, tmp_path / "BENCHMARK.json")
    p = _run(tmp_path)
    assert p.returncode not in (0, None), p.stderr
    assert p.stdout.strip() == ""


def test_unknown_workload(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "nope.nope",
         "--seed", "1", "--seconds", "1"], cwd=spec.ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 2 and p.stdout.strip() == ""
