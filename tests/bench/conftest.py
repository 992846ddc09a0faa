"""Shared pieces of the benchmark's tests: the checkout on ``sys.path``
(the harness lives in ``bench/`` beside ``src/``) and tiny versions of the
benchmark's configurations, small enough for the CPU."""

import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.harness import runner, spec  # noqa: E402

# PUBMED23's and GOOAQ's shapes cut to a CPU test: 4,096 rows of d = 32.
TINY_SEARCH = {
    "rows": 4096, "dim": 32,
    "forest": {"n_trees": 4, "bits": 4, "key_bits": 64, "leaf_size": 16,
               "seed": 0},
    "quantizer": {"bits": 4, "sample_limit": 262144},
    "search": {"k1": 64, "k2": 32, "h": 2, "k": 10},
    "data": {"generator": "lowrank", "n_clusters": 8, "rank": 4,
             "noise": 0.9},
    "control": {"bits": 2},
    "limits": {"recall": 0.5, "dist_gap": 0.14},
}
TINY_GRAPH = {
    "rows": 4096, "dim": 32,
    "forest": {"n_trees": 2, "bits": 4, "key_bits": 64, "leaf_size": 16,
               "seed": 0},
    "quantizer": {"bits": 4, "sample_limit": 262144},
    "graph": {"n_orders": 4, "k1": 16, "k2": 12, "k": 5, "seed": 0},
    "data": {"generator": "lowrank", "n_clusters": 8, "rank": 4,
             "noise": 0.9},
    "control": {"precision": "high"},
    "limits": {"recall": 0.5, "dist_gap": 2e-06},
}
TINY_TRAFFIC = {
    "task1_batch": {"rows_per_request": 64, "query_pool": 256,
                    "max_batch": 64},
    "task2_graph": {"check_rows": 256},
    "serve_ycsb_d": {"rate_per_s": 40, "query_pool": 256,
                     "setup_inserts": 64, "setup_deletes": 32,
                     "buffer_capacity": 512, "max_batch": 8},
}
SEED = 2 ** 33 + 5


def tiny_run(workload: str, seed: int = SEED, seconds: float = 1.0):
    """Run ``workload``'s driver on the CPU at the tiny size; returns the
    result line (without the harness's look for a chip)."""
    bench = spec.load_spec()
    cell = {w["name"]: w for w in bench["workloads"]}[workload]
    config = copy.deepcopy(TINY_GRAPH if cell["config"] == "gooaq"
                           else TINY_SEARCH)
    traffic = dict(spec.load_json(f"bench/traffic/{cell['traffic']}.json"),
                   **TINY_TRAFFIC[cell["traffic"]])
    driver = spec.load_module(f"bench/traffic/{traffic['driver']}.py",
                              f"tiny_{traffic['driver']}")
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    return runner.run_cell(cell, config, traffic, driver, [], e2e,
                           seed=seed, seconds=seconds, trace=False)


@pytest.fixture
def run_tiny():
    return tiny_run
