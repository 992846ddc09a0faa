"""Observability layer: spans, registry, dispatch/recompile accounting,
online recall probe, export surfaces — and the acceptance criterion that
turning all of it ON leaves engine results bit-identical.

Span/registry tests use private ``Tracer``/``MetricsRegistry`` instances
so they cannot interfere with the process-global ones the library
instrumentation writes to; dispatch-accounting tests read the global
registry through counter *deltas* for the same reason.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from repro.core.types import ForestConfig, SearchParams
from repro.data import ann_datasets
from repro.index import HilbertIndex, IndexConfig, MutableHilbertIndex
from repro.obs import (
    LatencyRecorder,
    MetricsRegistry,
    MetricsServer,
    RecallProbe,
    RecallProbeConfig,
    Tracer,
    default_registry,
    dispatch_counts,
    exact_topk,
    install_compile_listener,
    live_points,
    percentile_label,
    percentiles,
    recall_at_k,
    recompile_counts,
)
from repro import obs
from repro.obs.dispatch import dispatch_scope
from repro.serve import RetrievalEngine

N, D, Q = 2000, 32, 48

CFG = IndexConfig(
    forest=ForestConfig(n_trees=4, bits=4, key_bits=128, leaf_size=16, seed=0),
    query_chunk=16,
)
SP = SearchParams(k1=16, k2=64, h=1, k=10)


@pytest.fixture(scope="module")
def dataset():
    data, queries = ann_datasets.lowrank_dataset_with_queries(
        N, Q, D, n_clusters=8, seed=0
    )
    return np.asarray(data), np.asarray(queries)


@pytest.fixture(scope="module")
def static_index(dataset):
    data, _ = dataset
    return HilbertIndex.build(data, config=CFG)


# -- spans -------------------------------------------------------------------


def test_span_nesting_records_parent_chain():
    tr = Tracer(enabled=True)
    with tr.span("outer", phase="a") as outer:
        with tr.span("mid") as mid:
            with tr.span("inner"):
                pass
        assert tr.current() is outer
    assert tr.current() is None
    spans = tr.spans()
    # completion order: innermost exits first
    assert [s.name for s in spans] == ["inner", "mid", "outer"]
    inner, mid_s, outer_s = spans
    assert inner.parent_id == mid_s.span_id
    assert mid_s.parent_id == outer_s.span_id
    assert outer_s.parent_id is None
    assert outer_s.attrs == {"phase": "a"}
    assert all(s.wall_ms is not None and s.wall_ms >= 0 for s in spans)


def test_span_trees_stay_separate_across_threads():
    """Serve/maintenance-style interleaving: each thread roots its own
    tree; neither thread's spans parent into the other's."""
    tr = Tracer(enabled=True)
    barrier = threading.Barrier(2)

    def worker(tag):
        barrier.wait()
        for i in range(5):
            with tr.span(f"{tag}.outer", i=i):
                with tr.span(f"{tag}.inner"):
                    time.sleep(0.001)

    threads = [
        threading.Thread(target=worker, args=(t,)) for t in ("serve", "maint")
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = tr.spans()
    assert len(spans) == 20
    by_id = {s.span_id: s for s in spans}
    for s in spans:
        tag = s.name.split(".")[0]
        if s.parent_id is None:
            assert s.name == f"{tag}.outer"
        else:
            parent = by_id[s.parent_id]
            # parent is the same thread's outer span, never cross-thread
            assert parent.thread == s.thread
            assert parent.name == f"{tag}.outer"


def test_disabled_tracer_is_noop_and_enable_preserves_buffer():
    tr = Tracer(enabled=False)
    with tr.span("never") as s:
        s.set(k=1)  # noop span swallows attrs
    assert tr.spans() == []
    # global enable() must keep already-recorded spans (it resizes the
    # deque in place rather than replacing the tracer)
    prev = obs.default_tracer().enabled
    try:
        obs.enable()
        with obs.span("kept"):
            pass
        obs.enable(capacity=8192)
        assert any(s.name == "kept" for s in obs.default_tracer().spans())
    finally:
        obs.default_tracer().enabled = prev


def test_disabled_span_without_capture_is_the_shared_noop():
    from repro.obs.trace import _NOOP

    assert Tracer(enabled=False).span("engine.batch", rows=3) is _NOOP


@pytest.mark.parametrize("enabled", [False, True])
def test_span_lands_on_profiler_host_plane(tmp_path, enabled):
    """Under a profiler capture a span annotates the host plane, attrs as
    metadata, whether or not the tracer records it too."""
    import jax
    from jax.profiler import ProfileData

    tr = Tracer(enabled=enabled)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("engine.batch", rows=3) as s:
            s.set(epoch=7)
            jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    found = [
        (plane.name, dict(e.stats))
        for plane in ProfileData.from_file(str(path)).planes
        for line in plane.lines for e in line.events
        if e.name == "engine.batch"
    ]
    assert len(found) == 1
    plane, stats = found[0]
    assert plane.startswith("/host:")
    assert stats["rows"] == 3 and stats["epoch"] == 7
    assert [x.name for x in tr.spans()] == (["engine.batch"] if enabled
                                            else [])


def test_span_buffer_is_bounded():
    tr = Tracer(capacity=4, enabled=True)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    assert [s.name for s in tr.spans()] == ["s6", "s7", "s8", "s9"]


def test_chrome_trace_is_valid_monotonic_json():
    tr = Tracer(enabled=True)

    def worker():
        with tr.span("t2.root"):
            pass

    with tr.span("root", rows=3) as root:
        with tr.span("child"):
            pass
    t = threading.Thread(target=worker)
    t.start()
    t.join()
    doc = json.loads(json.dumps(tr.chrome_trace()))  # round-trips as JSON
    events = doc["traceEvents"]
    assert len(events) == 3
    ts = [e["ts"] for e in events]
    assert ts == sorted(ts), "timestamps must be monotonic"
    for e in events:
        assert e["ph"] == "X" and e["dur"] >= 0 and isinstance(e["tid"], int)
    by_name = {e["name"]: e for e in events}
    assert by_name["child"]["args"]["parent"] == root.span_id
    assert by_name["root"]["args"]["rows"] == 3
    # the two threads land on different tracks
    assert by_name["t2.root"]["tid"] != by_name["root"]["tid"]


# -- registry ----------------------------------------------------------------


def test_percentile_label_generalizes():
    assert percentile_label(50) == "p50"
    assert percentile_label(99) == "p99"
    assert percentile_label(99.9) == "p999"
    assert percentile_label(99.99) == "p9999"
    assert percentile_label(99.5) == "p995"
    assert percentile_label(0.5) == "p05"


def test_percentiles_nearest_rank():
    s = list(range(1, 101))  # 1..100
    out = percentiles(s, points=(50.0, 99.0, 99.9))
    assert out == {"p50": 50.0, "p99": 99.0, "p999": 100.0}
    assert percentiles([]) == {}


def test_latency_recorder_consistent_snapshot_under_writers():
    """The (count, window) pair must come from one lock acquisition:
    count below capacity implies exactly count retained samples."""
    rec = LatencyRecorder(capacity=10_000)
    stop = threading.Event()

    def writer():
        i = 0
        while not stop.is_set() and i < 5_000:
            rec.record(float(i))
            i += 1

    threads = [threading.Thread(target=writer) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        for _ in range(200):
            n, window = rec._consistent()
            assert window.size == min(n, 10_000)
            snap = rec.snapshot()
            assert snap["count"] >= window.size or snap["count"] >= n
    finally:
        stop.set()
        for t in threads:
            t.join()
    n, window = rec._consistent()
    assert window.size == min(n, 10_000)


def test_latency_recorder_samples_since_keeps_later_samples():
    rec = LatencyRecorder(capacity=4)
    for v in (1.0, 2.0):
        rec.record(v)
    t = time.perf_counter()
    for v in (3.0, 4.0, 5.0):
        rec.record(v)
    # the ring has wrapped: oldest first, 1.0 evicted
    np.testing.assert_array_equal(rec.samples(), [2.0, 3.0, 4.0, 5.0])
    np.testing.assert_array_equal(rec.samples(since=t), [3.0, 4.0, 5.0])
    np.testing.assert_array_equal(rec.samples(before=t), [2.0])
    assert rec.samples(since=time.perf_counter()).size == 0
    snap = rec.snapshot(points=(50.0,), since=t)
    assert snap == {"p50": 4.0, "count": 3.0, "mean": 4.0, "max": 5.0}
    assert rec.snapshot()["count"] == 5.0


def test_registry_find_never_registers():
    reg = MetricsRegistry()
    assert reg.find("lat_ms", phase="a") is None
    rec = reg.latency("lat_ms", phase="a")
    assert reg.find("lat_ms", phase="a") is rec
    assert reg.find("lat_ms") is None
    assert reg.snapshot() == {'lat_ms{phase="a"}': {"count": 0.0}}


def test_registry_get_or_create_and_type_guard():
    reg = MetricsRegistry()
    c = reg.counter("x_total", site="a")
    assert reg.counter("x_total", site="a") is c
    assert reg.counter("x_total", site="b") is not c
    with pytest.raises(TypeError):
        reg.gauge("x_total", site="a")
    g = reg.gauge("depth", fn=lambda: 7.0)
    assert g.value == 7.0
    # re-registering replaces the callback (newest owner wins)
    reg.gauge("depth", fn=lambda: 9.0)
    assert g.value == 9.0
    bad = reg.gauge("boom", fn=lambda: 1 / 0)
    assert np.isnan(bad.value)


def test_registry_snapshot_consistent_under_concurrent_writers():
    reg = MetricsRegistry()
    n_threads, n_incs = 4, 500
    lat = reg.latency("lat_ms", capacity=n_threads * n_incs)
    start = threading.Barrier(n_threads + 1)

    def writer(i):
        c = reg.counter("hits_total", worker=str(i))
        start.wait()
        for j in range(n_incs):
            c.inc()
            reg.counter("all_total").inc()
            lat.record(float(j))

    threads = [
        threading.Thread(target=writer, args=(i,)) for i in range(n_threads)
    ]
    for t in threads:
        t.start()
    start.wait()
    # snapshot + exposition concurrently with the writers: must not raise,
    # and every observed counter value must be internally plausible
    for _ in range(50):
        snap = reg.snapshot()
        total = snap.get("all_total", 0)
        assert 0 <= total <= n_threads * n_incs
        reg.prometheus_text()
    for t in threads:
        t.join()
    snap = reg.snapshot()
    assert snap["all_total"] == n_threads * n_incs
    for i in range(n_threads):
        assert snap[f'hits_total{{worker="{i}"}}'] == n_incs
    assert snap["lat_ms"]["count"] == n_threads * n_incs


def test_prometheus_text_exposition_shape():
    reg = MetricsRegistry()
    reg.counter("reqs_total", site="s").inc(3)
    reg.gauge("depth").set(2.5)
    lat = reg.latency("lat_ms")
    for v in (1.0, 2.0, 3.0, 4.0):
        lat.record(v)
    text = reg.prometheus_text()
    lines = text.strip().splitlines()
    assert "# TYPE reqs_total counter" in lines
    assert 'reqs_total{site="s"} 3' in lines
    assert "# TYPE depth gauge" in lines
    assert "depth 2.5" in lines
    assert "# TYPE lat_ms summary" in lines
    assert 'lat_ms{quantile="0.5"} 2.0' in lines
    assert "lat_ms_count 4" in lines
    # every non-comment line is `name[{labels}] value`
    import re

    pat = re.compile(
        r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? (NaN|[-+0-9.eE]+)$"
    )
    for line in lines:
        if not line.startswith("#"):
            assert pat.match(line), line


# -- dispatch / recompile accounting -----------------------------------------


def test_recompile_detector_fresh_shape_fires_bucket_hit_silent():
    """The live version of the pow2-bucket invariant: a fresh query-count
    bucket compiles once; re-hitting the bucket dispatches silently.

    jit caches are process-global, so "fresh" must hold against every test
    that ran before this one — the index here uses a dimensionality (29)
    no other test in the suite touches, making each bucket's first
    dispatch a guaranteed cache miss regardless of suite order."""
    if not install_compile_listener():
        pytest.skip("jax.monitoring duration listener unavailable")
    data, queries = ann_datasets.lowrank_dataset_with_queries(
        1100, Q, 29, n_clusters=8, seed=3
    )
    cfg = IndexConfig(
        forest=ForestConfig(
            n_trees=4, bits=4, key_bits=96, leaf_size=16, seed=0
        ),
        query_chunk=16,
    )
    index = HilbertIndex.build(np.asarray(data), config=cfg)
    queries = np.asarray(queries)
    site = "hilbert.search"

    def delta(fn):
        d0 = dispatch_counts().get(site, 0)
        r0 = recompile_counts().get(site, 0)
        fn()
        return (
            dispatch_counts().get(site, 0) - d0,
            recompile_counts().get(site, 0) - r0,
        )

    # warm the 16-bucket (3 chunks of 16; at most the first compiles)
    d, r = delta(lambda: index.search(queries, SP))
    assert d == 3 and r <= 1
    # same bucket again: dispatches tick, recompiles must not
    d, r = delta(lambda: index.search(queries[16:32], SP))
    assert d == 1 and r == 0
    # fresh pow2 bucket (5 -> pad 8): exactly one recompile
    d, r = delta(lambda: index.search(queries[:5], SP))
    assert d == 1 and r == 1
    # bucket hit (7 -> pad 8): silent
    d, r = delta(lambda: index.search(queries[:7], SP))
    assert d == 1 and r == 0


def test_dispatch_scope_attributes_compiles_to_the_dispatching_thread():
    """A compile on the maintenance thread must not leak into a scope
    concurrently open on the serve thread (thread-local deltas)."""
    if not install_compile_listener():
        pytest.skip("jax.monitoring duration listener unavailable")
    import jax
    import jax.numpy as jnp

    compiled = threading.Event()
    entered = threading.Event()

    def compiler():
        entered.wait(5.0)
        with dispatch_scope("obs.test.compiler"):
            # fresh callable + odd shape: guaranteed cache miss
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(37))
        compiled.set()

    t = threading.Thread(target=compiler)
    t.start()
    r0 = recompile_counts()
    with dispatch_scope("obs.test.bystander"):
        entered.set()
        assert compiled.wait(30.0)
    t.join()
    r1 = recompile_counts()
    assert r1.get("obs.test.compiler", 0) - r0.get("obs.test.compiler", 0) == 1
    assert r1.get("obs.test.bystander", 0) == r0.get("obs.test.bystander", 0)


# -- online recall probe -----------------------------------------------------


def test_live_points_masks_tombstones(dataset):
    data, _ = dataset
    mut = MutableHilbertIndex(CFG, buffer_capacity=256, max_segments=8)
    mut.insert(data[:1500])
    mut.delete(np.arange(0, 100, dtype=np.int64))
    mut.insert(data[1500:1510])  # lands in the write buffer
    ids, pts = live_points(mut)
    assert ids.size == 1500 - 100 + 10
    assert np.intersect1d(ids, np.arange(100)).size == 0
    assert 1505 in ids  # buffered rows included
    # points round-trip: every live id maps back to its source row
    lookup = {int(i): p for i, p in zip(ids, pts)}
    np.testing.assert_allclose(lookup[200], data[200], rtol=1e-6)
    np.testing.assert_allclose(lookup[1505], data[1505], rtol=1e-6)


def test_exact_topk_and_recall_at_k():
    pts = np.array([[0.0, 0.0], [1.0, 0.0], [2.0, 0.0], [3.0, 0.0]])
    ids = np.array([10, 11, 12, 13], dtype=np.int64)
    q = np.array([[0.1, 0.0]])
    exact = exact_topk(q, ids, pts, k=2)
    np.testing.assert_array_equal(exact, [[10, 11]])
    # k beyond the live count pads with -1 and recall divides by full k
    exact4 = exact_topk(q, ids[:1], pts[:1], k=3)
    np.testing.assert_array_equal(exact4, [[10, -1, -1]])
    r = recall_at_k(np.array([[10, 12]]), np.array([[10, 11]]))
    assert r.tolist() == [0.5]


def test_online_recall_matches_offline(dataset):
    """Acceptance criterion: the probe's rolling recall@k equals an
    offline exact evaluation of the same served results (±0.02; with a
    100% sample and a quiescent index they agree exactly)."""
    data, queries = dataset
    mut = MutableHilbertIndex(CFG, buffer_capacity=256, max_segments=8)
    mut.insert(data[:1500])
    mut.delete(np.arange(0, 50, dtype=np.int64))

    eng = RetrievalEngine(
        mut, SP, max_batch=16,
        recall=RecallProbeConfig(fraction=1.0, max_pending=16, seed=0),
    )
    direct_i, _ = mut.search(queries, SP)
    tickets = [eng.submit(queries[a:b]) for a, b in [(0, 16), (16, 48)]]
    while eng.step():
        pass
    scored = eng.score_recall()
    assert scored == Q
    online = eng.recall_probe.recall()

    ids, pts = live_points(mut)
    exact = exact_topk(queries, ids, pts, SP.k)
    offline = float(recall_at_k(np.asarray(direct_i), exact).mean())
    assert abs(online - offline) <= 0.02
    snap = default_registry().snapshot()
    assert snap["engine_recall_samples_total"] >= Q
    assert abs(snap["engine_recall_at_k"] - online) <= 1e-12
    for t in tickets:
        assert t.ids is not None


def test_recall_probe_sampling_and_backpressure(static_index, dataset):
    _, queries = dataset
    reg = MetricsRegistry()
    probe = RecallProbe(
        RecallProbeConfig(fraction=1.0, max_pending=2, seed=0), registry=reg
    )
    ids, _ = static_index.search(queries[:4], SP)
    for _ in range(5):
        probe.offer(queries[:4], np.asarray(ids), SP.k, static_index)
    snap = reg.snapshot()
    assert snap["engine_recall_batches_sampled_total"] == 2
    assert snap["engine_recall_batches_dropped_total"] == 3
    assert snap["engine_recall_pending_batches"] == 2
    assert probe.score_pending() == 8
    assert 0.0 <= probe.recall() <= 1.0
    # fraction=0 never samples
    never = RecallProbe(RecallProbeConfig(fraction=0.0), registry=MetricsRegistry())
    assert not never.offer(queries[:4], np.asarray(ids), SP.k, static_index)


# -- engine bit-identity with full observability on --------------------------


def test_step_mode_bit_identical_with_observability_enabled(
    static_index, dataset
):
    """Tracing + metrics + dispatch accounting + a 100% recall probe must
    not perturb results: every row equals the direct search, bit for bit."""
    _, queries = dataset
    direct_i, direct_d = static_index.search(queries, SP)
    tracer = obs.default_tracer()
    prev = tracer.enabled
    try:
        obs.enable()
        eng = RetrievalEngine(
            static_index, SP, max_batch=16,
            recall=RecallProbeConfig(fraction=1.0, max_pending=16, seed=0),
        )
        cuts = [0, 5, 8, 20, 21, 37, Q]
        tickets = [
            eng.submit(queries[a:b]) for a, b in zip(cuts[:-1], cuts[1:])
        ]
        while eng.step():
            pass
        eng.score_recall()
    finally:
        tracer.enabled = prev
    got_i = np.concatenate([t.ids for t in tickets])
    got_d = np.concatenate([t.dists for t in tickets])
    np.testing.assert_array_equal(got_i, np.asarray(direct_i))
    np.testing.assert_array_equal(got_d, np.asarray(direct_d))
    names = {s.name for s in tracer.spans()}
    assert {"engine.batch", "engine.search"} <= names
    snap = default_registry().snapshot()
    assert snap["engine_completed_total"] >= len(tickets)
    assert not np.isnan(snap["engine_recall_at_k"])


def test_step_mode_records_lock_waits_holds_and_build_phases(dataset):
    """The engine's writes record their exclusive holds, its searches
    their read waits, and a build each phase, all stamped after ``t0``."""
    data, queries = dataset
    reg = default_registry()
    t0 = time.perf_counter()
    index = MutableHilbertIndex.from_index(
        HilbertIndex.build(data[:1500], config=CFG), buffer_capacity=1024)
    for phase in ("quantization", "sketches", "forest", "master_sort"):
        ms = reg.find("index_build_ms", phase=phase).samples(since=t0)
        assert ms.size >= 1 and np.all(ms >= 0.0), phase
    eng = RetrievalEngine(index, SP, max_batch=16)
    eng.insert(data[1500:1600])
    eng.delete(np.arange(10))
    eng.submit(queries[:8])
    while eng.step():
        pass
    holds = reg.find("engine_rwlock_write_hold_ms").samples(since=t0)
    waits = reg.find("engine_rwlock_read_wait_ms").samples(since=t0)
    assert holds.size >= 2 and np.all(holds > 0.0)
    assert waits.size >= 1 and np.all(waits >= 0.0)
    assert reg.find("engine_search_ms").samples(since=t0).size >= 1


# -- export surface (HTTP) ---------------------------------------------------


def test_metrics_http_endpoint_serves_all_three_views():
    reg = MetricsRegistry()
    reg.counter("up_total").inc()
    reg.latency("ping_ms").record(1.5)
    tr = Tracer(enabled=True)
    with tr.span("http.test"):
        pass
    with MetricsServer(port=0, registry=reg, tracer=tr) as srv:
        text = urllib.request.urlopen(
            srv.url + "/metrics", timeout=10
        ).read().decode()
        assert "up_total 1" in text
        assert 'ping_ms{quantile="0.5"} 1.5' in text
        snap = json.loads(
            urllib.request.urlopen(srv.url + "/metrics.json", timeout=10).read()
        )
        assert snap["up_total"] == 1
        trace_doc = json.loads(
            urllib.request.urlopen(srv.url + "/trace", timeout=10).read()
        )
        assert [e["name"] for e in trace_doc["traceEvents"]] == ["http.test"]
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(srv.url + "/nope", timeout=10)
    # closed: further requests fail fast
    with pytest.raises(Exception):
        urllib.request.urlopen(srv.url + "/metrics", timeout=2)


# -- engine metrics mirror ---------------------------------------------------


def test_engine_metrics_mirror_into_registry():
    from repro.serve.metrics import EngineMetrics

    reg = MetricsRegistry()
    m = EngineMetrics(registry=reg)
    m.bump("admitted", 3)
    m.latency.record(4.0)
    m.queue_wait.record(1.0)
    assert m.counter("admitted") == 3
    snap = reg.snapshot()
    assert snap["engine_admitted_total"] == 3
    assert snap["engine_request_ms"]["count"] == 1.0
    assert snap["engine_queue_wait_ms"]["count"] == 1.0
    assert m.snapshot()["queue_wait_ms"]["count"] == 1.0
    # a second engine resets the per-engine view but the registry counter
    # keeps climbing (Prometheus monotonicity)
    m2 = EngineMetrics(registry=reg)
    m2.bump("admitted")
    assert m2.counter("admitted") == 1
    assert reg.snapshot()["engine_admitted_total"] == 4
