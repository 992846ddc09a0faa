"""``gooaq.task2_graph`` at a CPU size: a sound run is correct; the
control and each planted fault are not."""

import pytest
from bench_faults import FAULTS, plant

from bench.harness import controls

CELL = "gooaq.task2_graph"


def test_sound_run_is_correct(run_tiny):
    line = run_tiny(CELL)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"graph_build_s", "setup_s"}


def test_control_is_not_correct(run_tiny):
    from conftest import TINY_GRAPH

    with controls.installed(TINY_GRAPH["control"]):
        line = run_tiny(CELL)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_not_correct(run_tiny, monkeypatch, fault):
    from repro.core import knn_graph
    from repro.index import HilbertIndex

    if fault == "stale":
        # The state the orders merge into is returned unchanged.
        monkeypatch.setattr(knn_graph, "merge_order",
                            lambda best_id, best_dist, *a, **k:
                            (best_id, best_dist))
    else:
        plant(monkeypatch, HilbertIndex, "knn_graph", fault,
              lambda self: self.n_points)
    line = run_tiny(CELL)
    assert not line["correct"], line["checks"]
