"""``pubmed23.task1_batch`` at a CPU size: a sound run is correct; the
control and each planted fault are not."""

import pytest
from bench_faults import FAULTS, plant

from bench.harness import controls

CELL = "pubmed23.task1_batch"


def test_sound_run_is_correct(run_tiny):
    line = run_tiny(CELL)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"search_qps", "setup_s"}
    assert list(line)[-1] == "checks"


def test_control_is_not_correct(run_tiny):
    from conftest import TINY_SEARCH

    with controls.installed(TINY_SEARCH["control"]):
        line = run_tiny(CELL)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_not_correct(run_tiny, monkeypatch, fault):
    from repro.index import HilbertIndex

    plant(monkeypatch, HilbertIndex, "search", fault,
          lambda self: self.n_points)
    line = run_tiny(CELL)
    assert not line["correct"], line["checks"]
