"""The per-layer metrics read from the program's own recorders
(``bench/harness/counters.py``): each reads a number from its cell's
driver run into a ``runner.Run`` at the CPU size, and nothing from a
registry that holds no samples or whose recorders carry no stamps."""

import copy

import pytest
from conftest import SEED, TINY_SEARCH, TINY_TRAFFIC

from bench.harness import runner, spec

READERS = {
    "read_lock_wait_p95_ms.serve": "serve_ycsb_d",
    "write_hold_p95_ms.serve": "serve_ycsb_d",
    "batch_search_p50_ms.serve": "serve_ycsb_d",
    "build_forest_s.batch": "task1_batch",
}


def _reader(metric):
    return spec.load_module(f"bench/metrics/{metric}.py",
                            "counters_" + metric.replace(".", "_"))


@pytest.fixture(scope="module")
def readings():
    """Each reader's value right after its cell's driver ran (read before
    any other test can record into the process's registry)."""
    out = {}
    for traffic in sorted(set(READERS.values())):
        mix = dict(spec.load_json(f"bench/traffic/{traffic}.json"),
                   **TINY_TRAFFIC[traffic])
        run = runner.Run(f"pubmed23.{traffic}", copy.deepcopy(TINY_SEARCH),
                         mix, seed=SEED, seconds=1.0, trace=False)
        spec.load_module(f"bench/traffic/{mix['driver']}.py",
                         f"counters_{mix['driver']}").run(run)
        for metric, t in READERS.items():
            if t == traffic:
                out[metric] = (run, _reader(metric).read(run))
    return out


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_reads_its_cell(readings, metric):
    run, value = readings[metric]
    assert isinstance(value, float) and value >= 0.0, value
    if metric == "write_hold_p95_ms.serve":
        assert value > 0.0
    if metric == "build_forest_s.batch":
        assert value < run.setup_s


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_finds_nothing_in_an_empty_registry(readings, monkeypatch,
                                                   metric):
    from repro.obs import registry

    monkeypatch.setattr(registry, "_default", registry.MetricsRegistry())
    assert _reader(metric).read(readings[metric][0]) is None


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_finds_nothing_without_stamped_recorders(readings,
                                                        monkeypatch, metric):
    """A program whose registry cannot look a recorder up without making
    one (whose recorders carry no stamps) reads as nothing."""
    from repro.obs import registry

    class Unstamped(registry.MetricsRegistry):
        find = None

    monkeypatch.setattr(registry, "_default", Unstamped())
    assert _reader(metric).read(readings[metric][0]) is None


def test_window_excludes_set_up_samples(readings):
    """Set-up's own searches and writes are not in the window: the serve
    mix's warm-up searches and its two set-up writes precede it."""
    from repro.obs import default_registry

    run = readings["write_hold_p95_ms.serve"][0]
    start = run.t0 + run.setup_s
    holds = default_registry().find("engine_rwlock_write_hold_ms")
    assert holds.samples(before=start).size >= 2
    forest = default_registry().find("index_build_ms", phase="forest")
    assert forest.samples(before=start).size >= 1
