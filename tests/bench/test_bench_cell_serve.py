"""``pubmed23.serve_ycsb_d`` at a CPU size: a sound run is correct; the
control and each planted fault are not.  The faults run at a rate that
fills the engine's micro-batches, so that a batch has halves."""

import pytest
from bench_faults import FAULTS, plant

from bench.harness import controls

CELL = "pubmed23.serve_ycsb_d"


def test_sound_run_is_correct(run_tiny):
    line = run_tiny(CELL)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"request_p95_ms", "setup_s"}
    assert set(line["checks"]) == {"recall", "dist_gap", "bad_ids",
                                   "readback_miss"}


def test_control_is_not_correct(run_tiny):
    from conftest import TINY_SEARCH

    with controls.installed(TINY_SEARCH["control"]):
        line = run_tiny(CELL)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_not_correct(run_tiny, monkeypatch, fault):
    from conftest import TINY_TRAFFIC

    from repro.index import MutableHilbertIndex

    plant(monkeypatch, MutableHilbertIndex, "search", fault,
          lambda self: self._next_id)
    monkeypatch.setitem(TINY_TRAFFIC["serve_ycsb_d"], "rate_per_s", 1000)
    line = run_tiny(CELL)
    assert not line["correct"], line["checks"]
