"""The blocked sketch table that stage 1 reads on a TPU.

An index on a TPU keeps its W-word sketches in S-word slots (S = the next
power of two ≥ W), 128 / S to a 128-lane row, and stage 1 gathers whole
rows and keeps each candidate's slot; elsewhere it keeps the (n, W) rows.
These tests pin that the read returns exactly the (n, W) rows, that search,
graph construction and persistence give the same on either layout (the
blocked one forced here on the CPU), and that the on-disk layout stays
(n, W).
"""

import contextlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint
from repro.core import forest as forest_lib
from repro.core import knn_graph as knn_graph_lib
from repro.core import quantize, search as search_lib, sketch
from repro.data import ann_datasets
from repro.index import (
    ForestConfig,
    GraphParams,
    HilbertIndex,
    IndexConfig,
    SearchParams,
)
from repro.kernels.hamming import ops as hamming_ops

N, D, Q = 1500, 96, 21
CFG = IndexConfig(
    forest=ForestConfig(n_trees=3, bits=4, key_bits=128, leaf_size=16, seed=3)
)
SP = SearchParams(k1=16, k2=48, h=2, k=10)
GP = GraphParams(n_orders=4, k1=8, k2=16, k=6)


@pytest.fixture(scope="module")
def dataset():
    data, queries = ann_datasets.lowrank_dataset_with_queries(
        N, Q, D, n_clusters=6, seed=4
    )
    return jnp.asarray(data), jnp.asarray(queries)


@contextlib.contextmanager
def _layout(layout):
    """Indexes built or loaded inside keep ``layout``: the TPU's blocked
    table, or the (n, W) rows every other device keeps."""
    with pytest.MonkeyPatch.context() as mp:
        if layout == "blocked":
            mp.setattr(sketch, "resident_table", sketch.block_sketches)
        yield


@pytest.fixture(scope="module")
def index(dataset):
    """The index as a TPU keeps it: a blocked sketch table."""
    data, _ = dataset
    with _layout("blocked"):
        return HilbertIndex.build(data, CFG)


def _random_sketches(n, w, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 2**32, (n, w), dtype=np.uint32)


def _positions(n, shape, seed):
    """Random positions that include the first and the last one."""
    rng = np.random.default_rng(seed + 1)
    pos = rng.integers(0, n, shape).astype(np.int32)
    pos[0, 0], pos[-1, -1] = 0, n - 1
    return pos


@pytest.mark.parametrize("w", [12, 16])
@pytest.mark.parametrize("n", [1000, 1024, 1031])
def test_blocked_read_returns_the_rows(n, w):
    sk = _random_sketches(n, w, seed=n + w)
    table = sketch.block_sketches(jnp.asarray(sk))
    per_row = 128 // 16
    assert table.shape == (-(-n // per_row), 128)
    assert table.dtype == jnp.uint32
    # pad words and the never-addressed pad slots of the last row are zero
    slots = np.asarray(table).reshape(-1, 16)
    assert not slots[:, w:].any()
    assert not slots[n:].any()
    pos = _positions(n, (7, 37), seed=n)
    want = sk[pos]
    got = sketch.read_blocked(table, jnp.asarray(pos), w)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(
        np.asarray(sketch.unblock_sketches(table, n, w)), sk
    )


@pytest.fixture(params=["vmem", "hbm"])
def route(request, monkeypatch):
    """The kernel route's two reads of a blocked table: the table held in
    VMEM, or rows gathered from HBM (the CPU has no VMEM, so the route is
    forced here either way)."""
    # Traced afresh under the forced route; programs of either route must
    # not outlive the fixture.
    jax.clear_caches()
    monkeypatch.setattr(hamming_ops, "table_in_vmem",
                        lambda *a: request.param == "vmem")
    yield request.param
    monkeypatch.undo()
    jax.clear_caches()


@pytest.mark.parametrize("w", [12, 16])
@pytest.mark.parametrize("n", [1000, 1031])
def test_kernel_read_returns_the_rows(route, n, w):
    """The Pallas read (interpret mode here): word-major, padded to the
    row kernels' tiles."""
    sk = _random_sketches(n, w, seed=n * w)
    table = sketch.block_sketches(jnp.asarray(sk))
    pos = _positions(n, (7, 37), seed=n + 1)
    wm = np.asarray(hamming_ops.gather_sketches(table, jnp.asarray(pos), w=w))
    assert wm.shape == (8, w, 128)
    np.testing.assert_array_equal(wm[:7, :, :37], sk[pos].transpose(0, 2, 1))


@pytest.mark.parametrize("w", [1, 3, 5, 200])
def test_blocked_layout_follows_the_width(w):
    n = 37
    sk = _random_sketches(n, w, seed=w)
    s = sketch.slot_words(w)
    assert s >= w and s & (s - 1) == 0 and s < 2 * w + 1
    table = sketch.block_sketches(jnp.asarray(sk))
    assert table.shape[1] == max(128, s)
    pos = _positions(n, (3, 11), seed=w)
    np.testing.assert_array_equal(
        np.asarray(sketch.read_blocked(table, jnp.asarray(pos), w)), sk[pos]
    )


def test_index_keeps_one_blocked_table(index):
    w = sketch.sketch_words(D)
    assert index.sketches_master.shape == (-(-N * 4 // 128), 128)
    view = np.asarray(index.sketches_view())
    assert view.shape == (N, w)
    rep = index.memory_report()
    assert rep["sketch_table_bytes"] == index.sketches_master.nbytes
    # the paper's model counts n·W words whatever the layout
    assert rep["sketch_bytes"] == N * w * 4


def test_resident_table_blocks_only_on_a_tpu(dataset, index):
    """Off a TPU an (n, W) row is one contiguous read already, so the
    index keeps the rows as they are, unpadded: the same rows the blocked
    table holds."""
    sk = jnp.asarray(_random_sketches(100, 12, seed=5))
    assert jax.default_backend() != "tpu"
    assert sketch.resident_table(sk) is sk
    rows = HilbertIndex.build(dataset[0], CFG)
    w = sketch.sketch_words(D)
    assert rows.sketches_master.shape == (N, w)
    np.testing.assert_array_equal(np.asarray(rows.sketches_master),
                                  np.asarray(index.sketches_view()))
    rep = rows.memory_report()
    assert rep["sketch_table_bytes"] == rep["sketch_bytes"] == N * w * 4
    assert not sketch.is_blocked(sk, 12)
    assert sketch.is_blocked(sketch.block_sketches(sk), 12)
    # at W = 128 the two layouts are one array
    wide = jnp.asarray(_random_sketches(10, 128, seed=6))
    np.testing.assert_array_equal(
        np.asarray(sketch.block_sketches(wide)), np.asarray(wide))


@pytest.mark.parametrize("w", [12, 128])
def test_kernel_read_of_rows_returns_the_rows(w):
    """gather_sketches on an (n, W) table: the plain row gather,
    word-major and padded as from a blocked table."""
    n = 300
    sk = _random_sketches(n, w, seed=w)
    pos = _positions(n, (7, 37), seed=w)
    wm = np.asarray(hamming_ops.gather_sketches(jnp.asarray(sk),
                                                jnp.asarray(pos), w=w))
    assert wm.shape == (8, w, 128)
    np.testing.assert_array_equal(wm[:7, :, :37], sk[pos].transpose(0, 2, 1))
    np.testing.assert_array_equal(
        np.asarray(sketch.read_sketches(jnp.asarray(sk), jnp.asarray(pos), w)),
        sk[pos])


def _unblocked_reference(index, queries, params):
    """Algorithm 1 as it ran on the (n, W) table: per tree, gather each
    candidate's sketch row, Hamming filter, merge; then unpacked stage 2."""
    fcfg = index.config.forest
    f = index.forest
    sk = index.sketches_view()
    qsk = sketch.make_sketches(index.quant, queries)
    qn = queries.shape[0]
    best_pos = jnp.full((qn, params.k2), -1, jnp.int32)
    best_dist = jnp.full((qn, params.k2), jnp.int32(2**30), jnp.int32)
    for t in range(f.n_trees):
        cand = forest_lib.tree_candidates(
            queries, f.orders[t], f.directories[t], f.lo, f.hi, f.perms[t],
            f.flips[t], bits=fcfg.bits, key_bits=fcfg.key_bits,
            leaf_size=fcfg.leaf_size, k1=params.k1,
        )
        mpos = index.master_rank[cand]
        hd = sketch.hamming_distance(qsk[:, None, :], sk[mpos])
        best_pos, best_dist = search_lib._merge_topk_dedup(
            best_pos, best_dist, mpos, hd, params.k2
        )
    codes_u8 = quantize.unpack_codes(index.codes_master, index.dim)
    return search_lib.stage2_expand_rank(
        queries, best_pos, codes_u8, index.master_order, index.quant,
        h=params.h, k=params.k,
    )


def test_search_matches_the_unblocked_table(dataset, index):
    """Fused chunk and per-tree reference path on the index's table give
    the (n, W) table's ids and distances, bit for bit."""
    _, queries = dataset
    ids_r, d2_r = _unblocked_reference(index, queries, SP)
    for fused in (True, False):
        ids, d2 = index.search(queries, SP, backend="xla", fused=fused)
        np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_r))
        np.testing.assert_array_equal(np.asarray(d2), np.asarray(d2_r))


def test_stage1_kernel_route_is_bit_identical(route, dataset, index):
    """Stage 1's state is integer: the Pallas read + Hamming kernel
    (interpret mode here) and the XLA read agree exactly, tree by tree."""
    _, queries = dataset
    fcfg = CFG.forest
    f = index.forest
    qsk = sketch.make_sketches(index.quant, queries)
    state = {
        use: (jnp.full((Q, SP.k2), -1, jnp.int32),
              jnp.full((Q, SP.k2), jnp.int32(2**30), jnp.int32))
        for use in (False, True)
    }
    for t in range(f.n_trees):
        for use in (False, True):
            state[use] = search_lib.stage1_tree_merge(
                queries, qsk, *state[use],
                f.orders[t], f.directories[t], f.lo, f.hi, f.perms[t],
                f.flips[t], index.master_rank, index.sketches_master,
                bits=fcfg.bits, key_bits=fcfg.key_bits,
                leaf_size=fcfg.leaf_size, k1=SP.k1, k2=SP.k2,
                use_kernels=use,
            )
        for a, b in zip(state[False], state[True]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_knn_graph_matches_the_unblocked_sketches(dataset, index):
    """Graph construction reads the same (n, W) sketches in point-id order
    as before: those the quantizer's codes give."""
    data, _ = dataset
    codes = quantize.encode(index.quant, data)
    sketches_ids = sketch.sketches_from_codes(codes, bits=CFG.quantizer.bits)
    np.testing.assert_array_equal(
        np.asarray(index.sketches_view()[index.master_rank]),
        np.asarray(sketches_ids),
    )
    fcfg = CFG.forest
    ids_r, d2_r = knn_graph_lib.knn_graph_from_sketches(
        data, sketches_ids, GP, bits=fcfg.bits, key_bits=fcfg.key_bits,
        lo=index.forest.lo, hi=index.forest.hi,
    )
    ids, d2 = index.knn_graph(GP)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_r))
    np.testing.assert_array_equal(np.asarray(d2), np.asarray(d2_r))


def test_save_load_keeps_the_disk_layout(tmp_path, dataset, index):
    """On disk the sketches stay (n, W) uint32, so checkpoints written
    before the blocked table load unchanged; load lays them out for the
    device again."""
    _, queries = dataset
    path = str(tmp_path / "idx")
    index.save(path)
    step = checkpoint.latest_step(path)
    with open(os.path.join(path, f"step_{step:08d}", "manifest.json")) as f:
        manifest = json.load(f)
    assert manifest["extra"]["format_version"] == 2
    assert manifest["leaves"]["['sketches_master']"] == [
        [N, sketch.sketch_words(D)], "uint32"]
    with _layout("blocked"):
        loaded = HilbertIndex.load(path)
    np.testing.assert_array_equal(
        np.asarray(loaded.sketches_master), np.asarray(index.sketches_master)
    )
    # loaded here, off a TPU, the same checkpoint keeps (n, W) rows
    rows = HilbertIndex.load(path)
    assert rows.sketches_master.shape == (N, sketch.sketch_words(D))
    ids, d2 = index.search(queries, SP)
    for other in (loaded, rows):
        ids2, d22 = other.search(queries, SP)
        np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids2))
        np.testing.assert_array_equal(np.asarray(d2), np.asarray(d22))
