"""Concurrency stress + read-path purity for the reader-writer serving path.

Three layers, all deterministic in their ASSERTIONS even where threads
race freely in between:

* ``ReadWriteLock`` unit semantics — shared readers, exclusive writers,
  write re-entrancy, read-under-write, upgrade refusal, writer
  preference (what makes the serve path starvation-free for swaps).
* Read-path purity — the precondition for the shared read side: a
  facade's ``search(..., allow_rewrite=False)`` must not mutate ANY
  internal state once warm (fingerprinted field-by-field before/after a
  concurrent hammering).  Exemptions are documented where declared:
  ``last_dispatch_count`` (a diagnostic scalar assigned once per search)
  and jit-cache recency ORDER (``BoundedJitCache.keys()`` is
  fingerprinted as a set).
* The stress battery — barrier-started reader threads + a paced writer +
  forced maintenance through >= 3 epoch swaps: every ticket is acked
  (zero drops), probe-window tickets are bit-equal to a direct search on
  the exact index version (epoch) that served them, and the
  ``deadlock_watchdog`` fixture (tests/conftest.py) turns any
  lock-ordering hang into a full thread dump instead of a silent CI
  timeout.
"""

import threading
import time

import jax
import numpy as np
import pytest

from repro.core.types import ForestConfig, SearchParams
from repro.data import ann_datasets
from repro.index import IndexConfig, MutableHilbertIndex
from repro.serve import RetrievalEngine
from repro.serve.rwlock import ReadWriteLock

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


def _mutable(data, n=1200, deletes=True):
    mut = MutableHilbertIndex(CFG, buffer_capacity=256, max_segments=8)
    ids = mut.insert(data[:n])
    if deletes:
        mut.delete(ids[::7])  # tombstones: dead-count caches get exercised
    return mut


# -- ReadWriteLock semantics -------------------------------------------------


def test_rwlock_readers_share():
    lock = ReadWriteLock()
    inside = threading.Barrier(3, action=lambda: None)

    def reader():
        with lock.read_locked():
            inside.wait(timeout=10)  # all 3 hold the read side AT ONCE

    threads = [threading.Thread(target=reader) for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    assert not any(t.is_alive() for t in threads)
    assert lock.readers == 0


def test_rwlock_writer_excludes_readers():
    lock = ReadWriteLock()
    observed = []
    entered = threading.Event()
    release = threading.Event()

    def writer():
        with lock.write_locked():
            entered.set()
            release.wait(10)
            observed.append("write-exit")

    def reader():
        entered.wait(10)
        with lock.read_locked():
            observed.append("read")

    tw = threading.Thread(target=writer)
    tr = threading.Thread(target=reader)
    tw.start()
    tr.start()
    entered.wait(10)
    time.sleep(0.05)  # give the reader time to (wrongly) slip in
    assert observed == []  # reader is blocked out
    release.set()
    tw.join(10)
    tr.join(10)
    assert observed == ["write-exit", "read"]


def test_rwlock_write_reentrancy_and_read_under_write():
    lock = ReadWriteLock()
    with lock.write_locked():
        assert lock.write_held()
        with lock.write_locked():       # re-entrant write
            with lock.read_locked():    # read under own write: allowed
                assert lock.write_held()
    assert not lock.write_held()
    assert lock.readers == 0


def test_rwlock_upgrade_refused():
    lock = ReadWriteLock()
    with lock.read_locked():
        with pytest.raises(RuntimeError, match="upgrade"):
            lock.acquire_write()
    # the failed upgrade must not have corrupted state
    with lock.write_locked():
        pass


def test_rwlock_writer_preference_gates_new_readers():
    """A PENDING writer blocks new readers (swaps cannot be starved by a
    steady reader stream), while already-reading threads re-enter freely."""
    lock = ReadWriteLock()
    r1_in = threading.Event()
    r1_go = threading.Event()
    w_done = threading.Event()
    order = []

    def long_reader():
        with lock.read_locked():
            r1_in.set()
            r1_go.wait(10)
            with lock.read_locked():  # re-entrant: bypasses the writer gate
                order.append("reentrant-read")

    def writer():
        with lock.write_locked():
            order.append("write")
        w_done.set()

    def late_reader():
        # arrives while the writer is pending: must wait BEHIND it
        with lock.read_locked():
            order.append("late-read")

    t1 = threading.Thread(target=long_reader)
    t1.start()
    r1_in.wait(10)
    tw = threading.Thread(target=writer)
    tw.start()
    while lock.stats()["pending_writers"] == 0:
        time.sleep(0.001)
    t3 = threading.Thread(target=late_reader)
    t3.start()
    time.sleep(0.05)
    assert "late-read" not in order  # gated by the pending writer
    r1_go.set()
    for t in (t1, tw, t3):
        t.join(10)
    assert order.index("write") < order.index("late-read")
    assert "reentrant-read" in order


def test_rwlock_stats_accounting():
    lock = ReadWriteLock()
    with lock.write_locked():
        time.sleep(0.01)
    with lock.read_locked():
        s = lock.stats()
        assert s["readers"] == 1
    s = lock.stats()
    assert s["read_acquisitions"] >= 1
    assert s["write_acquisitions"] >= 1
    assert s["write_held_ms"] >= 5.0


def test_rwlock_observer_sees_waits_and_each_outer_hold():
    seen = []
    lock = ReadWriteLock(observer=lambda kind, ms: seen.append((kind, ms)))
    with lock.write_locked():
        with lock.write_locked(), lock.read_locked():
            pass                        # re-entrant: not a hold of its own
        time.sleep(0.01)
    with lock.read_locked():
        pass
    assert [k for k, _ in seen] == ["write", "hold", "read"]
    assert seen[1][1] >= 5.0
    assert abs(seen[1][1] - lock.stats()["write_held_ms"]) < 1e-9


# -- read-path purity --------------------------------------------------------


def _fingerprint_mutable(idx):
    """Every mutable field the search path could conceivably touch.

    ``seg.dead_cache``/``dead_epoch`` ARE included: the warm-up search
    fills them, after which a pure read path must leave them fixed.
    """
    lsm = idx._lsm
    segs = tuple(
        (id(seg), seg.gen, seg.n_valid, seg.dead_cache, seg.dead_epoch,
         id(seg.index), seg.ids.tobytes())
        for seg in idx.segments
    )
    return (
        int(idx._buf_count), int(idx._gen), int(lsm.next_id),
        int(lsm.delete_epoch), lsm.alive.tobytes(),
        None if idx._buf_points is None else idx._buf_points.tobytes(),
        None if idx._buf_ids is None else idx._buf_ids.tobytes(),
        segs,
    )


def _hammer(search_fn, n_threads=4, n_iters=6):
    """Run ``search_fn(thread_idx, iter_idx)`` from N barrier-started
    threads; returns collected results, raises on any thread error."""
    barrier = threading.Barrier(n_threads)
    errors = []
    results = [[] for _ in range(n_threads)]

    def run(i):
        try:
            barrier.wait(timeout=30)
            for j in range(n_iters):
                results[i].append(search_fn(i, j))
        except BaseException as e:  # surfaced below
            errors.append(e)

    threads = [
        threading.Thread(target=run, args=(i,)) for i in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not any(t.is_alive() for t in threads), "hammer threads hung"
    if errors:
        raise errors[0]
    return results


def test_mutable_read_path_is_pure_under_concurrency(dataset):
    data, queries = dataset
    idx = _mutable(data)
    # warm up: fills dead-count caches and compiles dispatches
    want_i, want_d = idx.search(queries, SP, allow_rewrite=False)
    want_i, want_d = np.asarray(want_i), np.asarray(want_d)
    before = _fingerprint_mutable(idx)

    def do_search(i, j):
        ids, dists = idx.search(queries, SP, allow_rewrite=False)
        return np.asarray(ids), np.asarray(dists)

    results = _hammer(do_search)
    assert _fingerprint_mutable(idx) == before
    for per_thread in results:
        for ids, dists in per_thread:
            np.testing.assert_array_equal(ids, want_i)
            np.testing.assert_array_equal(dists, want_d)


def test_mutable_rewrite_suppression_surfaces_as_pressure(dataset):
    """allow_rewrite=False must not shrink segments even under heavy
    tombstone pressure — the condition surfaces via rewrite_pressure()
    for the maintenance path instead."""
    data, _ = dataset
    idx = MutableHilbertIndex(CFG, buffer_capacity=64, max_segments=8)
    ids = idx.insert(data[:256])
    idx.delete(ids[:200])  # most of every segment is dead
    tight = SearchParams(k1=16, k2=4, h=1, k=4)  # tiny candidate pool
    assert idx.rewrite_pressure(tight) > 0
    before = _fingerprint_mutable(idx)
    idx.search(data[:8], tight, allow_rewrite=False)
    assert _fingerprint_mutable(idx) == before  # suppressed: no rewrite
    assert idx.rewrite_pressure(tight) > 0      # still pending for maint
    idx.search(data[:8], tight)                 # default path DOES rewrite
    assert _fingerprint_mutable(idx) != before


@pytest.mark.skipif(
    jax.device_count() < 2,
    reason="sharded purity needs >= 2 devices (CI sets "
    "xla_force_host_platform_device_count=8)",
)
def test_sharded_mutable_read_path_is_pure_under_concurrency(dataset):
    from repro.index.sharded_mutable import ShardedMutableHilbertIndex
    from repro.launch.mesh import data_mesh

    data, queries = dataset
    mesh = data_mesh(jax.device_count())
    idx = ShardedMutableHilbertIndex(
        CFG, mesh=mesh, buffer_capacity=64, max_segments=8
    )
    ids = idx.insert(data[:1200])  # seals generations (64-row buffers)
    idx.delete(ids[::7])
    idx.insert(data[1200:1500])

    def fingerprint():
        segs = tuple(
            (id(seg), seg.gen, seg.dead_cache, seg.dead_epoch,
             seg.ids_host.tobytes())
            for seg in idx.segments
        )
        return (
            int(idx._rr), int(idx._gen), int(idx._lsm.next_id),
            int(idx._lsm.delete_epoch), idx._lsm.alive.tobytes(),
            None if idx._buf_pts is None else idx._buf_pts.tobytes(),
            None if idx._buf_ids is None else idx._buf_ids.tobytes(),
            idx._buf_count.tobytes(),
            idx._alive_key, id(idx._alive_dev), id(idx._dev_buf),
            frozenset(idx._chunk_fns.keys()),  # recency ORDER exempt
            segs,
        )

    want_i, want_d = idx.search(queries, SP, allow_rewrite=False)  # warm
    want_i, want_d = np.asarray(want_i), np.asarray(want_d)
    before = fingerprint()

    def do_search(i, j):
        ids_, dists_ = idx.search(queries, SP, allow_rewrite=False)
        return np.asarray(ids_), np.asarray(dists_)

    results = _hammer(do_search, n_threads=3, n_iters=4)
    # last_dispatch_count is the DOCUMENTED exemption (diagnostic scalar,
    # assigned once at search end) — everything else must be untouched
    assert fingerprint() == before
    for per_thread in results:
        for ids_, dists_ in per_thread:
            np.testing.assert_array_equal(ids_, want_i)
            np.testing.assert_array_equal(dists_, want_d)


# -- the stress battery ------------------------------------------------------


def test_stress_readers_writer_and_epoch_swaps(dataset, deadlock_watchdog):
    """Barrier-started readers + a paced writer + forced maintenance.

    Per round: writer burst (concurrent with readers) -> writer
    quiesces -> probe window (readers still hammering; probe tickets
    recorded with the epoch's index) -> forced maintenance swap.  After
    three rounds:

    * >= 3 epoch swaps happened,
    * every admitted ticket completed (zero dropped acks),
    * every probe ticket is BIT-EQUAL to a direct search on the exact
      index version (epoch) that served it — the old epoch's index is
      never mutated again once the writer quiesced, so the comparison is
      exact even though the engine swapped on.
    """
    deadlock_watchdog(300.0)
    data, queries = dataset
    idx = _mutable(data, n=1000, deletes=False)
    rng = np.random.default_rng(42)
    extra = rng.normal(size=(2000, D)).astype(np.float32)
    eng = RetrievalEngine(
        idx, SP, maintenance=None, serve_threads=2, max_batch=16,
        start=True,
    )
    stop = threading.Event()
    reader_errors = []
    reader_counts = [0] * 3
    barrier = threading.Barrier(len(reader_counts) + 1)

    def reader(i):
        r = np.random.default_rng(i)
        try:
            barrier.wait(timeout=30)
            while not stop.is_set():
                a = int(r.integers(0, Q - 8))
                t = eng.submit(queries[a : a + 8])
                ids, dists = t.result(timeout=120)
                assert ids.shape == (8, SP.k)
                assert dists.shape == (8, SP.k)
                reader_counts[i] += 1
        except BaseException as e:
            reader_errors.append(e)
            stop.set()

    threads = [
        threading.Thread(target=reader, args=(i,), daemon=True)
        for i in range(len(reader_counts))
    ]
    for t in threads:
        t.start()
    barrier.wait(timeout=30)

    probes = []  # (ticket, expected_epoch, index_version)
    swaps = 0
    off = 0
    try:
        for _ in range(3):
            # writer burst: inserts + deletes race the readers
            for _ in range(2):
                new_ids = eng.insert(extra[off : off + 300])
                off += 300
                eng.delete(new_ids[::5])
            # writer quiesces; the CURRENT epoch's index is now frozen
            epoch_index = eng.index
            epoch = eng.epoch
            round_probes = [
                eng.submit(queries[a : a + 8]) for a in range(0, 40, 8)
            ]
            for t in round_probes:
                t.result(timeout=120)
                probes.append((t, epoch, epoch_index))
            # forced maintenance: compact the shadow, replay, swap
            assert eng.maintain_once(force=True)
            swaps += 1
            assert eng.epoch == epoch + 1
    finally:
        stop.set()
        for t in threads:
            t.join(60)
        eng.stop()

    assert not reader_errors, reader_errors[:1]
    assert not any(t.is_alive() for t in threads), "reader threads hung"
    assert swaps >= 3
    assert all(c > 0 for c in reader_counts)
    # zero dropped acks: everything admitted was completed (no deadlines
    # in this battery, so nothing may expire either)
    m = eng.metrics
    assert m.counter("completed") == m.counter("admitted")
    assert m.counter("deadline_expired") == 0
    assert eng._write_log is None  # replay log closed after every cycle
    # per-epoch bit-equality: the engine searched with
    # allow_rewrite=False, so the direct comparison does too
    for t, epoch, epoch_index in probes:
        assert t.epoch == epoch
        want_i, want_d = epoch_index.search(
            t.queries, SP, allow_rewrite=False
        )
        np.testing.assert_array_equal(t.ids, np.asarray(want_i))
        np.testing.assert_array_equal(t.dists, np.asarray(want_d))


def test_serve_threads_share_the_read_side(dataset, deadlock_watchdog):
    """With serve_threads=2 and no writer, a burst drains with both
    workers searching CONCURRENTLY under the shared read lock — and the
    results are still bit-equal to direct search."""
    deadlock_watchdog(180.0)
    data, queries = dataset
    idx = _mutable(data, n=800, deletes=False)
    want_i, want_d = idx.search(queries[:8], SP, allow_rewrite=False)
    with RetrievalEngine(
        idx, SP, maintenance=None, serve_threads=2, max_batch=8,
        start=True,
    ) as eng:
        tickets = [eng.submit(queries[:8]) for _ in range(24)]
        for t in tickets:
            ids, dists = t.result(timeout=120)
            np.testing.assert_array_equal(ids, np.asarray(want_i))
            np.testing.assert_array_equal(dists, np.asarray(want_d))
    s = eng._serve_lock.stats()
    assert s["read_acquisitions"] >= len(tickets) / eng.max_batch


def test_edf_order_is_visible_in_step_mode(dataset):
    """A near-deadline ticket submitted AFTER a far-deadline bulk one is
    served first (the FIFO head-blocking case EDF removes)."""
    data, queries = dataset
    idx = _mutable(data, n=600, deletes=False)
    eng = RetrievalEngine(idx, SP, maintenance=None, max_batch=8)
    bulk = eng.submit(queries[:8], deadline_ms=60_000.0)
    urgent = eng.submit(queries[8:16], deadline_ms=500.0)
    assert eng.step() > 0
    assert urgent.done and not bulk.done
    assert eng.step() > 0
    assert bulk.done
    bulk.result(0), urgent.result(0)
