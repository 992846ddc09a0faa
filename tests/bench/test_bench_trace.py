"""The trace-to-metric reduction: on hand-made events, and on a small
trace recorded on a TPU v5e through the harness's own capture (a 2-tree
index of 4,096 rows at d = 384: one 8-query search on the Pallas route,
a 20 ms pause, one one-order k-NN graph), committed beside this file."""

import importlib.util
import os

import numpy as np
import pytest

from bench.harness import trace as T
from bench.harness.spec import BENCH_DIR

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "window.xplane.pb")


class _E:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.end_ns = start + dur


class _L:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _P:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


HAMMING = ('%branch_0_fun.8 = s32[256,256]{1,0:T(8,128)} custom-call('
           'u32[256,12,1]{2,1,0:T(8,128)} %copy.1, u32[256,12,256]{2,1,0:'
           'T(8,128)} %b.2), custom_call_target="tpu_custom_call", '
           'operand_layout_constraints={}')


def _fake():
    device = _P("/device:TPU:0", [
        _L("XLA Modules", [_E("jit_a(1)", 100, 50), _E("jit_b(2)", 200, 100),
                           _E("jit_a(1)", 400, 50)]),
        _L("XLA Ops", [_E("%fusion.3 = f32[2]{0} fusion(f32[2]{0} %p)",
                          110, 20),
                       _E("%while.1 = (s32[]) while((s32[]) %t)", 200, 100),
                       _E(HAMMING, 210, 30)]),
    ])
    host = _P("/host:CPU", [_L("python", [_E("bench.window", 90, 400),
                                          _E("bench.submit", 150, 100),
                                          _E("other", 0, 10)])])
    return T.Trace([device, host], keep_op=lambda n: "tpu_custom" in n)


def test_intervals():
    assert T.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert T.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert T.gaps([(0, 10)], 2, 5) == []


def test_names():
    assert T.module_base("jit_merge_order(3026558858167966956)") == \
        "jit_merge_order"
    assert T.op_base("%fusion.12 = f32[2]{0} fusion(f32[2]{0} %x)") == \
        "fusion"
    assert T.op_base(HAMMING) == "tpu_custom_call->s32[256,256]"
    assert T.kernel_signature(HAMMING) == ("s32", ["u32", "u32"])
    assert T.kernel_signature("%f.1 = f32[2]{0} fusion()") is None


def test_reduction_on_made_events(monkeypatch):
    monkeypatch.setattr(T, "EDGE_NS", 5)
    t = _fake()
    assert t.window == (90, 490)
    assert t.window_s == pytest.approx(400e-9)
    assert t.busy_s() == pytest.approx(200e-9)       # 50 + 100 + 50
    # The last program may have been cut short by the trace's stop.
    assert t.module_stats("jit_a") == (pytest.approx(50e-9), 1)
    assert t.module_stats("jit_b") == (pytest.approx(100e-9), 1)
    assert t.op_stats(lambda n: True) == (pytest.approx(30e-9), 1)
    b = t.breakdown()
    assert [n for n, _ in b["device_ops"]] == [
        "jit_b/tpu_custom_call->s32[256,256]", "jit_a/fusion"]
    # Gaps: 90-100 (window), 150-200 (submit), 300-400 (window), 450-490.
    assert b["idle_gaps"][0] == ["bench.window", pytest.approx(100e-9)]
    assert ["bench.submit", pytest.approx(50e-9)] in b["idle_gaps"]


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        T.Trace([_P("/host:CPU", [_L("python", [_E("bench.window", 0, 1)])])])


@pytest.fixture(scope="module")
def recorded():
    return T.Trace.from_file(RECORDED,
                             keep_op=lambda n: "tpu_custom_call" in n)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH_DIR, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_recorded_window_and_busy(recorded):
    assert 0.02 < recorded.window_s < 5
    busy = recorded.busy_s()
    assert 0 < busy < recorded.window_s
    # Busy is the union of program executions clipped to the window.
    lo, hi = recorded.window
    mods = recorded.modules[0]
    cover = np.zeros(int(hi - lo) // 1000 + 1, bool)
    for _, s, e in mods:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            cover[int(a - lo) // 1000:int(b - lo) // 1000] = True
    assert busy == pytest.approx(cover.sum() * 1e-6, rel=0.02)


def test_recorded_programs_and_kernels(recorded):
    assert recorded.module_stats("jit_fused_search_chunk")[1] == 1
    assert recorded.module_stats("jit_merge_order")[1] == 1
    assert recorded.module_stats("jit_lexsort_words")[1] == 1
    ham = _reader("hamming_roofline.batch")
    qd = _reader("qdist_roofline.batch")
    assert recorded.op_stats(ham.is_kernel)[1] == 2      # one per tree
    assert recorded.op_stats(qd.is_kernel)[1] == 1
    b = recorded.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert any(n.startswith("jit_fused_search_chunk/")
               for n, _ in b["device_ops"])
    names = {n for n, _ in b["idle_gaps"]}
    assert names <= {"bench.window", "bench.search", "bench.graph"}
    assert b["idle_gaps"][0][1] > 0.015          # the 20 ms pause


class _Run:
    def __init__(self, trace, shapes):
        self.trace, self.record = trace, {"shapes": shapes}

    def peaks(self):
        return {"hbm_bytes_per_s": 819e9}


def test_recorded_readers(recorded):
    shapes = {"queries": 8, "k1": 128, "k2": 16, "h": 1, "dim": 384,
              "levels": 16}
    run = _Run(recorded, shapes)
    for name in ("hamming_roofline.batch", "qdist_roofline.batch"):
        share = _reader(name).read(run)
        assert 0 < share <= 100
    assert _reader("search_chunk_ms.batch").read(run) > 0
    assert _reader("graph_merge_ms.graph").read(run) > 0
    assert _reader("hilbert_sort_ms.graph").read(run) > 0
    idle = _reader("idle_share.batch").read(run)
    assert 0 < idle < 100
    assert _reader("search_chunk_ms.batch").read(_Run(None, shapes)) is None
