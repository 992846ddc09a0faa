"""Tests for ``repro.index.MutableHilbertIndex`` (LSM streaming mutation).

Core contract: after ANY insert/delete/flush/compact sequence, search over
the mutable index is at least as good as a from-scratch
``HilbertIndex.build`` over the surviving points — and after a full
``compact()`` it is *equivalent* (same sorted distance profile; same ids up
to ADC-distance ties), because compaction rebuilds over the live points in
insertion order via the same fast path.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st
from repro.data import ann_datasets
from repro.index import (
    ForestConfig,
    HilbertIndex,
    IndexConfig,
    MutableHilbertIndex,
    SearchParams,
)

N, D, Q = 2000, 32, 24

CFG = IndexConfig(
    forest=ForestConfig(n_trees=4, bits=4, key_bits=128, leaf_size=16, seed=0)
)
SP = SearchParams(k1=16, k2=64, h=1, k=10)


@pytest.fixture(scope="module")
def dataset():
    data, queries = ann_datasets.lowrank_dataset_with_queries(
        N, Q, D, n_clusters=8, seed=0
    )
    return np.asarray(data), jnp.asarray(queries)


def _recall_vs_exact(ext_ids, live_ids, live_pts, queries, k):
    """recall@k of external-id results against exact kNN over live points."""
    gt, _ = ann_datasets.exact_knn(live_pts, np.asarray(queries), k)
    pos_of = {int(e): i for i, e in enumerate(live_ids)}
    pos = np.asarray(
        [[pos_of.get(int(e), -1) for e in row] for row in np.asarray(ext_ids)]
    )
    return ann_datasets.recall_at_k(pos, gt), pos


# -- streaming equivalence ---------------------------------------------------


def test_streamed_equals_fresh_build_after_compact(dataset):
    """Insert in batches + delete + compact == fresh build over survivors."""
    data, queries = dataset
    mut = MutableHilbertIndex(CFG, buffer_capacity=300, max_segments=4)
    ids_a = mut.insert(data[:1200])
    mut.delete(ids_a[50:150])
    ids_b = mut.insert(data[1200:])
    mut.compact()
    assert mut.n_segments == 1
    assert mut.n_live == N - 100

    live_mask = np.ones(N, bool)
    live_mask[50:150] = False
    fresh = HilbertIndex.build(jnp.asarray(data[live_mask]), CFG)
    fids, fd2 = fresh.search(queries, SP)
    mids, md2 = mut.search(queries, SP)
    # Identical sorted distance profiles...
    assert np.array_equal(np.asarray(md2), np.asarray(fd2))
    # ...and identical ids: fresh position p holds the point whose external
    # id is live_ids[p], so mapping fresh results through live_ids must
    # reproduce the mutable results exactly.
    live_ids = np.concatenate([ids_a, ids_b])[live_mask]
    assert np.array_equal(live_ids[np.asarray(fids)], np.asarray(mids))


def test_multisegment_recall_at_least_fresh(dataset):
    """Un-compacted LSM state (segments + buffer + tombstones) loses nothing."""
    data, queries = dataset
    mut = MutableHilbertIndex(CFG, buffer_capacity=256, max_segments=6)
    ids = mut.insert(data)
    rng = np.random.default_rng(1)
    dead = rng.choice(N, 200, replace=False)
    mut.delete(ids[dead])
    mut.insert(data[:100])  # re-add some points (new ids, still live)
    assert mut.n_segments > 1

    live_mask = np.ones(N, bool)
    live_mask[dead] = False
    live_ids = np.concatenate([ids[live_mask], np.arange(N, N + 100)])
    live_pts = np.concatenate([data[live_mask], data[:100]])
    rec_mut, _ = _recall_vs_exact(
        mut.search(queries, SP)[0], live_ids, live_pts, queries, SP.k
    )
    fresh = HilbertIndex.build(jnp.asarray(live_pts), CFG)
    rec_fresh, _ = _recall_vs_exact(
        np.arange(len(live_pts))[np.asarray(fresh.search(queries, SP)[0])],
        np.arange(len(live_pts)), live_pts, queries, SP.k,
    )
    assert rec_mut >= rec_fresh


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
@settings(max_examples=5, deadline=None)
@given(
    seed=st.integers(0, 2**16),
    batches=st.lists(st.integers(40, 300), min_size=1, max_size=4),
    delete_frac=st.floats(0.0, 0.4),
    capacity=st.integers(64, 256),
)
def test_streaming_equivalence_property(seed, batches, delete_frac, capacity):
    """Property: any insert/delete/compact stream matches a fresh build."""
    rng = np.random.default_rng(seed)
    n = sum(batches)
    data = rng.normal(size=(n, 16)).astype(np.float32)
    queries = jnp.asarray(rng.normal(size=(8, 16)).astype(np.float32))
    cfg = IndexConfig(
        forest=ForestConfig(n_trees=2, bits=4, key_bits=64, leaf_size=8, seed=0)
    )
    sp = SearchParams(k1=8, k2=32, h=1, k=5)

    mut = MutableHilbertIndex(cfg, buffer_capacity=capacity, max_segments=3)
    all_ids, start = [], 0
    for b in batches:
        ids = mut.insert(data[start : start + b])
        all_ids.append(ids)
        n_del = int(delete_frac * b)
        if n_del:
            mut.delete(rng.choice(ids, n_del, replace=False))
        start += b
    mut.compact()

    all_ids = np.concatenate(all_ids)
    live = mut._alive[all_ids]
    assert mut.n_live == int(live.sum())
    if mut.n_live == 0:
        mids, md2 = mut.search(queries, sp)
        assert (np.asarray(mids) == -1).all()
        return
    fresh = HilbertIndex.build(jnp.asarray(data[live]), cfg)
    _, fd2 = fresh.search(queries, sp)
    mids, md2 = mut.search(queries, sp)
    k_pad = max(0, sp.k - mut.n_live)  # fresh build has no -1 padding
    if k_pad == 0:
        assert np.array_equal(np.asarray(md2), np.asarray(fd2))
    else:
        assert np.isinf(np.asarray(md2)[:, sp.k - k_pad :]).all()
    # every returned non-padding id is live
    ret = np.asarray(mids)
    assert mut._alive[ret[ret >= 0]].all()


# -- tombstone edge cases ----------------------------------------------------


def test_delete_then_reinsert(dataset):
    data, _ = dataset
    mut = MutableHilbertIndex(CFG, buffer_capacity=128)
    ids = mut.insert(data[:64])
    assert mut.delete(ids[:32]) == 32
    assert mut.delete(ids[:32]) == 0  # idempotent
    ids2 = mut.insert(data[:32])  # same vectors, NEW identities
    assert not np.intersect1d(ids, ids2).size or (ids2 > ids.max()).all()
    q = jnp.asarray(data[:4])
    hits, d2 = mut.search(q, dataclasses.replace(SP, k=4))
    hits = np.asarray(hits)
    assert not np.isin(hits, ids[:32]).any()  # tombstoned ids never surface
    # each query point's own reinserted copy comes back at distance ~0
    assert np.asarray(d2)[:, 0] == pytest.approx(0.0, abs=1e-3)
    assert (hits[np.arange(4), 0] == ids2[np.arange(4)]).all()


def test_delete_entire_segment_and_compact(dataset):
    data, queries = dataset
    mut = MutableHilbertIndex(CFG, buffer_capacity=100, max_segments=10)
    ids_a = mut.insert(data[:100])  # seals segment A exactly
    ids_b = mut.insert(data[100:200])  # seals segment B
    assert mut.n_segments == 2
    mut.delete(ids_a)  # entire segment A dead
    hits, _ = mut.search(queries, SP)
    hits = np.asarray(hits)
    assert not np.isin(hits, ids_a).any()
    assert np.isin(hits[hits >= 0], ids_b).all()
    mut.compact()
    assert mut.n_segments == 1  # dead segment physically gone
    assert mut.segments[0].n_points == 100
    assert np.array_equal(mut.segments[0].ids, ids_b)


def test_search_k_exceeds_live_points(dataset):
    data, queries = dataset
    mut = MutableHilbertIndex(CFG, buffer_capacity=16)
    ids = mut.insert(data[:24])  # one segment of 16 + 8 buffered
    mut.delete(ids[20:])
    params = dataclasses.replace(SP, k=30)  # k=30 > 20 live
    hits, d2 = mut.search(queries, params)
    hits, d2 = np.asarray(hits), np.asarray(d2)
    assert hits.shape == (Q, 30)
    # exactly the 20 live ids come back, then -1/inf padding
    for row, drow in zip(hits, d2):
        assert set(row[row >= 0].tolist()) == set(ids[:20].tolist())
        assert (row[20:] == -1).all() and np.isinf(drow[20:]).all()
    # empty index: all padding
    empty = MutableHilbertIndex(CFG)
    ehits, ed2 = empty.search(queries, SP)
    assert (np.asarray(ehits) == -1).all() and np.isinf(np.asarray(ed2)).all()


def test_flush_drops_dead_buffer_rows(dataset):
    data, _ = dataset
    mut = MutableHilbertIndex(CFG, buffer_capacity=512)
    ids = mut.insert(data[:64])
    mut.delete(ids)
    assert mut.flush() is None  # fully tombstoned buffer seals nothing
    assert mut.n_segments == 0 and mut.n_buffered == 0


# -- persistence and values --------------------------------------------------


def test_save_load_roundtrip_and_continue(tmp_path, dataset):
    data, queries = dataset
    mut = MutableHilbertIndex(CFG, buffer_capacity=300, max_segments=4)
    ids = mut.insert(data[:1000], values=np.arange(1000, dtype=np.int32) % 17)
    mut.delete(ids[::7])
    mut.insert(data[1000:1100],
               values=np.arange(1000, 1100, dtype=np.int32) % 17)
    h1, d1 = mut.search(queries, SP)
    mut.save(str(tmp_path / "m"))
    loaded = MutableHilbertIndex.load(str(tmp_path / "m"))
    assert loaded.config == mut.config
    assert loaded.n_live == mut.n_live and loaded.n_segments == mut.n_segments
    h2, d2 = loaded.search(queries, SP)
    assert np.array_equal(np.asarray(h1), np.asarray(h2))
    assert np.array_equal(np.asarray(d1), np.asarray(d2))
    assert np.array_equal(
        np.asarray(loaded.values_at(h1)), np.asarray(mut.values_at(h1))
    )
    # the loaded index keeps streaming: insert/delete/compact all work
    loaded.insert(data[1100:1200],
                  values=np.arange(1100, 1200, dtype=np.int32) % 17)
    loaded.compact()
    assert loaded.n_segments == 1
    with pytest.raises(ValueError, match="kind"):
        from repro.index import load_mutable_bundle

        load_mutable_bundle(str(tmp_path / "m"), kind="retrieval_store")
    with pytest.raises(FileNotFoundError):
        MutableHilbertIndex.load(str(tmp_path / "missing"))


def test_resave_to_same_path_is_nondestructive(tmp_path, dataset):
    """A newer save never rewrites bundles an older manifest references."""
    import shutil

    data, queries = dataset
    path = str(tmp_path / "m")
    mut = MutableHilbertIndex(CFG, buffer_capacity=200, max_segments=8)
    ids = mut.insert(data[:500])
    mut.save(path)
    h1, d1 = mut.search(queries, SP)
    manifest_v1 = (tmp_path / "m" / "mutable_manifest.json").read_bytes()
    # mutate heavily and save again over the same directory
    mut.delete(ids[:250])
    mut.insert(data[500:900])
    mut.compact()
    mut.save(path)
    h2, d2 = mut.search(queries, SP)
    loaded2 = MutableHilbertIndex.load(path)
    assert np.array_equal(np.asarray(loaded2.search(queries, SP)[0]),
                          np.asarray(h2))
    # simulate a crash BEFORE the v2 manifest rename: restore the v1
    # manifest — everything it references must still be intact on disk.
    (tmp_path / "m" / "mutable_manifest.json").write_bytes(manifest_v1)
    loaded1 = MutableHilbertIndex.load(path)
    assert loaded1.n_live == 500 and loaded1.n_deleted == 0
    assert np.array_equal(np.asarray(loaded1.search(queries, SP)[0]),
                          np.asarray(h1))
    assert np.array_equal(np.asarray(loaded1.search(queries, SP)[1]),
                          np.asarray(d1))
    shutil.rmtree(path)


def test_save_over_foreign_checkpoint_never_keeps_stale_segments(tmp_path,
                                                                 dataset):
    """Segment dedup is content-addressed: same path + same shape/ids but
    different points must be rewritten, not skipped."""
    data, queries = dataset
    path = str(tmp_path / "m")
    a = MutableHilbertIndex(CFG, buffer_capacity=512)
    a.bulk_load(data[:200])
    a.save(path)
    # a different process rebuilds from a different corpus of the SAME size:
    # identical gen, n_points, and external ids 0..199.
    b = MutableHilbertIndex(CFG, buffer_capacity=512)
    b.bulk_load(data[200:400])
    b.save(path)
    loaded = MutableHilbertIndex.load(path)
    hb, db = b.search(queries, SP)
    hl, dl = loaded.search(queries, SP)
    assert np.array_equal(np.asarray(hb), np.asarray(hl))
    assert np.array_equal(np.asarray(db), np.asarray(dl))


def test_saves_prune_unreferenced_bundles(tmp_path, dataset):
    """Disk usage is bounded: only current+previous manifest bundles remain."""
    import os

    data, _ = dataset
    path = str(tmp_path / "m")
    mut = MutableHilbertIndex(CFG, buffer_capacity=100, max_segments=10)
    for i in range(4):
        mut.insert(data[i * 100 : (i + 1) * 100])
        mut.compact()  # new gen each round; older segment becomes garbage
        mut.save(path)
    state_steps = [n for n in os.listdir(os.path.join(path, "state"))
                   if n.startswith("step_")]
    seg_dirs = os.listdir(os.path.join(path, "segments"))
    assert len(state_steps) <= 2 and len(seg_dirs) <= 2
    assert MutableHilbertIndex.load(path).n_live == 400


def test_heavily_tombstoned_segment_rewritten_on_read(dataset):
    """Once tombstones exceed the stage-2 pool, search rewrites the segment
    instead of letting dead candidates crowd out live neighbors."""
    data, queries = dataset
    cfg = IndexConfig(forest=CFG.forest)
    sp = dataclasses.replace(SP, k2=32, h=1, k=10)  # pool cap = 96
    mut = MutableHilbertIndex(cfg, buffer_capacity=200)
    ids = mut.insert(data[:200])  # one sealed segment
    assert mut.n_segments == 1
    gen_before = mut.segments[0].gen
    mut.delete(ids[:150])  # dead=150 > cap-k=86
    hits, d2 = mut.search(queries, sp)
    assert mut.segments[0].gen != gen_before  # rewritten in place
    assert mut.segments[0].n_points == 50  # tombstones physically dropped
    hits = np.asarray(hits)
    assert np.isin(hits[hits >= 0], ids[150:]).all()
    # store_points=False can't rewrite: must degrade gracefully, not crash
    slim = MutableHilbertIndex(
        IndexConfig(forest=CFG.forest, store_points=False), buffer_capacity=200
    )
    sids = slim.insert(data[:200])
    slim.delete(sids[:150])
    shits, _ = slim.search(queries, sp)
    assert not np.isin(np.asarray(shits), sids[:150]).any()


def test_legacy_static_retrieval_checkpoint_still_loads(tmp_path, dataset):
    """One-release compat: PR-1-format store bundles load via from_index."""
    from repro.index import save_index_bundle
    from repro.serve.retrieval import RetrievalStore

    data, queries = dataset
    static = HilbertIndex.build(
        jnp.asarray(data[:500]),
        IndexConfig(forest=CFG.forest, store_points=False),
    )
    values = np.arange(500, dtype=np.int32) % 11
    save_index_bundle(  # exactly what the old RetrievalStore.save wrote
        static, str(tmp_path / "old"), kind="retrieval_store",
        extra_arrays={"values": jnp.asarray(values)},
    )
    store = RetrievalStore.load(str(tmp_path / "old"))
    ids, _ = store.lookup(queries, SP)
    sids, _ = static.search(queries, SP)
    assert np.array_equal(np.asarray(ids), np.asarray(sids))
    assert np.array_equal(np.asarray(store.values), values)
    store.append(jnp.asarray(data[500:510]),
                 jnp.asarray(np.arange(10, dtype=np.int32)))
    assert store.index.n_live == 510


def test_failed_first_insert_does_not_pin_values_mode(dataset):
    data, _ = dataset
    mut = MutableHilbertIndex(CFG)
    with pytest.raises(ValueError, match="values must be"):
        mut.insert(data[:10], values=np.arange(3))
    mut.insert(data[:10])  # valueless mode still available
    assert mut._track_values is False


def test_failed_insert_leaves_state_unchanged(dataset):
    """A rejected insert must not advance ids or desync values/alive."""
    data, _ = dataset
    mut = MutableHilbertIndex(CFG, buffer_capacity=128)
    mut.insert(data[:10], values=np.arange(10, dtype=np.int32))
    with pytest.raises(ValueError, match="values must be"):
        mut.insert(data[10:20], values=np.arange(7, dtype=np.int32))
    assert mut.n_live == 10 and mut._next_id == 10
    ids = mut.insert(data[10:20], values=np.arange(10, 20, dtype=np.int32))
    assert np.array_equal(ids, np.arange(10, 20))
    assert np.array_equal(
        np.asarray(mut.values_at(ids)), np.arange(10, 20)
    )


def test_from_index_without_values_pins_valueless_mode(dataset):
    data, _ = dataset
    base = HilbertIndex.build(jnp.asarray(data[:100]), CFG)
    mut = MutableHilbertIndex.from_index(base)
    with pytest.raises(ValueError, match="values"):
        mut.insert(data[100:110], values=np.arange(10))
    assert mut._next_id == 100  # the rejected insert assigned nothing


def test_store_points_false_serves_but_cannot_compact(dataset):
    """store_points=False saves segment RAM; compaction degrades gracefully."""
    data, queries = dataset
    slim_cfg = IndexConfig(forest=CFG.forest, store_points=False)
    mut = MutableHilbertIndex(slim_cfg, buffer_capacity=100, max_segments=2)
    mut.insert(data[:500])  # exceeds max_segments; tier merge must not crash
    assert mut.n_segments >= 2
    assert all(s.index.points is None for s in mut.segments)
    hits, _ = mut.search(queries, SP)
    assert np.asarray(hits).shape == (Q, SP.k)
    with pytest.raises(ValueError, match="store_points"):
        mut.compact()
    fat = MutableHilbertIndex(CFG, buffer_capacity=100)
    fat.insert(data[:500])
    slim_b = mut.memory_report()["segments_bytes"]
    fat_b = fat.memory_report()["segments_bytes"]
    assert slim_b < fat_b  # the raw points are the difference


def test_values_tracking_is_all_or_nothing(dataset):
    data, _ = dataset
    mut = MutableHilbertIndex(CFG)
    mut.insert(data[:8], values=np.arange(8))
    with pytest.raises(ValueError, match="values"):
        mut.insert(data[8:16])
    plain = MutableHilbertIndex(CFG)
    plain.insert(data[:8])
    with pytest.raises(ValueError, match="values"):
        plain.insert(data[8:16], values=np.arange(8))
    with pytest.raises(ValueError, match="values"):
        plain.values_at(np.array([0]))


def test_from_index_adoption(dataset):
    data, queries = dataset
    base = HilbertIndex.build(jnp.asarray(data[:500]), CFG)
    mut = MutableHilbertIndex.from_index(base, buffer_capacity=64)
    assert mut.n_live == 500 and mut.n_segments == 1
    new_ids = mut.insert(data[500:550])
    mut.delete(np.arange(10))
    hits, _ = mut.search(queries, SP)
    hits = np.asarray(hits)
    assert not np.isin(hits, np.arange(10)).any()
    assert mut.n_live == 540
    assert (new_ids >= 500).all()


def test_segment_k_inflation_is_pow2_bucketed(dataset, monkeypatch):
    """A segment is searched for k + pow2(dead): delete counts in one bucket
    reuse one compiled search instead of recompiling per delete batch."""
    data, queries = dataset
    base = HilbertIndex.build(jnp.asarray(data[:500]), CFG)
    mut = MutableHilbertIndex.from_index(base, buffer_capacity=64)
    seen = []
    real = HilbertIndex.search

    def spy(self, q, params=None, **kw):
        seen.append(params.k)
        return real(self, q, params, **kw)

    monkeypatch.setattr(HilbertIndex, "search", spy)
    ks = []
    for dead in (0, 5, 8, 9, 16):
        mut.delete(np.arange(dead)[np.asarray(mut._alive[:dead])])
        seen.clear()
        hits, _ = mut.search(queries, SP)
        assert not np.isin(np.asarray(hits), np.arange(dead)).any()
        ks.append(seen[-1])
    assert ks == [SP.k, SP.k + 8, SP.k + 8, SP.k + 16, SP.k + 16]


# -- reporting / repr / defaults --------------------------------------------


def test_memory_report_accounts_everything(dataset):
    data, _ = dataset
    mut = MutableHilbertIndex(CFG, buffer_capacity=256)
    mut.insert(data[:600], values=np.arange(600, dtype=np.int32))
    rep = mut.memory_report()
    assert rep["segments_bytes"] == sum(rep["per_segment"])
    assert rep["buffer_bytes"] > 0  # preallocated buffer counted
    assert rep["values_bytes"] == 600 * 4
    assert rep["tombstone_bytes"] == 600
    assert rep["total_bytes"] == (
        rep["segments_bytes"] + rep["buffer_bytes"]
        + rep["values_bytes"] + rep["tombstone_bytes"]
    )
    # segment accounting includes the stored points + codes + sketches
    seg = mut.segments[0]
    seg_rep = seg.index.memory_report()
    assert seg_rep["resident_bytes"] >= (
        seg_rep["points_bytes"] + seg_rep["codes_bytes"]
        + seg_rep["sketch_bytes"] + seg_rep["order_bytes"]
    )


def test_reprs_are_legible(dataset):
    data, _ = dataset
    idx = HilbertIndex.build(jnp.asarray(data[:300]), CFG)
    r = repr(idx)
    assert "n_points=300" in r and "MB" in r and "forest" not in r.lower()
    mut = MutableHilbertIndex(CFG, buffer_capacity=128)
    mut.insert(data[:300])
    mr = repr(mut)
    assert "n_segments=2" in mr and "n_live=300" in mr
    # segment lists print legibly (one short line per segment index)
    assert "n_points=128" in repr(mut.segments)


def test_no_shared_mutable_default_config(dataset):
    """``build(points)`` uses a None sentinel, not a shared default instance."""
    import inspect

    data, _ = dataset
    for fn in (HilbertIndex.build,):
        assert inspect.signature(fn).parameters["config"].default is None
    from repro.index.facade import build_with_timings
    assert (
        inspect.signature(build_with_timings).parameters["config"].default
        is None
    )
    from repro.serve.retrieval import RetrievalStore
    assert (
        inspect.signature(RetrievalStore.build).parameters["config"].default
        is None
    )
    idx = HilbertIndex.build(jnp.asarray(data[:100]))
    assert idx.config == IndexConfig()


# -- serving store -----------------------------------------------------------


def test_retrieval_store_append_delete(tmp_path, dataset):
    from repro.serve.retrieval import RetrievalStore

    data, queries = dataset
    vals = np.arange(1000, dtype=np.int32) % 31
    store = RetrievalStore.build(
        jnp.asarray(data[:1000]), jnp.asarray(vals),
        IndexConfig(forest=CFG.forest), buffer_capacity=256,
    )
    ids1, _ = store.lookup(queries, SP)
    # grow while serving: appended entries are searchable immediately
    new_ids = store.append(
        jnp.asarray(queries), jnp.asarray(np.full(Q, 7, np.int32))
    )
    ids2, d22 = store.lookup(queries, SP)
    assert (np.asarray(ids2)[:, 0] == new_ids).all()  # exact self-match
    assert np.asarray(d22)[:, 0] == pytest.approx(0.0, abs=1e-3)
    assert (np.asarray(store.index.values_at(ids2[:, :1])) == 7).all()
    # shrink while serving
    store.delete(new_ids)
    ids3, _ = store.lookup(queries, SP)
    assert np.array_equal(np.asarray(ids3), np.asarray(ids1))
    # persistence round-trip, then keep appending
    store.compact()
    store.save(str(tmp_path / "rs"))
    loaded = RetrievalStore.load(str(tmp_path / "rs"))
    ids4, _ = loaded.lookup(queries, SP)
    assert np.array_equal(np.asarray(ids4), np.asarray(ids1))
    loaded.append(jnp.asarray(data[:10]), jnp.asarray(vals[:10]))
    assert loaded.index.n_live == 1010


def test_buffer_exact_search_matmul_runs_at_highest_precision():
    """The write buffer's exact search must not inherit a TPU's one-pass
    bf16 default for f32 matmuls: every dot it lowers asks for HIGHEST."""
    from repro.core import search as search_lib

    text = search_lib.brute_force_topk.lower(
        jnp.zeros((4, 8), jnp.float32), jnp.zeros((16, 8), jnp.float32),
        jnp.ones((16,), bool), k=3,
    ).as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert dots and all("precision = [HIGHEST, HIGHEST]" in line
                        for line in dots), dots
