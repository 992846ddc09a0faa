"""Algorithm 1 jitted stages: approximate k-NN search with a Hilbert forest.

.. note::
   The public entry point is :class:`repro.index.HilbertIndex` — a
   self-describing facade that carries its build config, so search never
   takes a config argument.  This module now holds the **pure jitted
   stages** the facade composes, plus thin deprecation shims
   (:func:`build_index` / :func:`search`) for one release of backward
   compatibility.

Pipeline (paper §3.1): forest candidates (coarse) → Hamming filter on shared
sketches (fine) → master-order ±h expansion → asymmetric fp32-vs-4-bit
distance → top-k.

Implementation notes vs the pseudocode:
  * The paper first collects ALL n·k1 candidates per query, then filters.
    At challenge scale that transient alone is ~9 GB; we instead keep a
    running sketch-filtered top-k2 and merge each tree's k1 candidates into
    it — identical result (top-k2 of a union is associative), constant
    memory, and the same trick the paper itself uses for Task 2.
  * Candidates are tracked by their **master-order position** so stage 2 is
    a contiguous ±h window and all gathers hit the master-rearranged arrays
    (the paper's memory-locality trick; on TPU this turns into coalesced
    gathers over the sorted copies).
  * Duplicates (same point from several trees / overlapping windows) are
    deduped during the merge so the final top-k can't contain repeats.

Fused scan pipeline (the serving hot path): :func:`fused_search_chunk` runs
the WHOLE per-chunk pipeline — query sketching, a ``lax.scan`` over the
stacked forest arrays (``orders``/``directories``/``perms``/``flips``) that
replaces the per-tree Python loop, and the packed-code stage 2 — inside ONE
jitted computation, so a query chunk costs one XLA dispatch regardless of
``n_trees``.  Stage 2 reads candidate codes as contiguous ±h **windowed
dynamic slices** from the nibble-packed ``(n, ceil(d/8))`` uint32 resident
codes (half the HBM traffic of unpacked uint8) instead of a ``(Q, C, d)``
random gather; on TPU the window distances route through the Pallas
``qdist_windows_from_packed`` kernel, elsewhere through a packed XLA path
that unpacks losslessly and is therefore bit-identical to unpacked ADC.
"""

from __future__ import annotations

import functools
import warnings
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import forest as forest_lib
from repro.core import quantize, sketch
from repro.core.types import ForestConfig, QuantizerConfig, SearchParams

__all__ = [
    "HilbertForestIndex",
    "build_index",
    "search",
    "hilbert_master_sort",
    "stage1_tree_merge",
    "stage2_expand_rank",
    "stage2_packed_windows",
    "fused_search_chunk",
    "merge_topk",
    "brute_force_topk",
    "inflate_k",
    "paper_memory_model",
]

_INF = jnp.int32(2**30)


def paper_memory_model(n: int, d: int, sketch_bytes: int, forest_bytes: int
                       ) -> dict:
    """The paper's RAM-budget table (§3.1) as a dict of byte counts.

    Single source of truth for both the legacy container's and the facade's
    ``memory_report`` (previously copy-pasted).  ``quantized_bytes`` is the
    4-bit-packed accounting — since PR 3 the codes are RESIDENT in exactly
    this layout, so it equals the actual ``codes_master.nbytes``.
    """
    packed_codes = n * (-(-d // 8)) * 4  # 4-bit packed into uint32 words
    shared = n * (-(-d // 32)) * 4  # MSB plane counted once
    return {
        "forest_bytes": forest_bytes,
        "sketch_bytes": sketch_bytes,
        "quantized_bytes": packed_codes,
        "shared_bit_savings": shared,
        "combined_stage2_bytes": sketch_bytes + packed_codes - shared,
    }


class HilbertForestIndex(NamedTuple):
    """DEPRECATED legacy container — use :class:`repro.index.HilbertIndex`.

    Carries no config, so callers of the legacy :func:`search` must re-supply
    the exact build-time ``ForestConfig`` (the footgun the facade removes).
    Codes here stay UNPACKED (n, d) uint8 for one release of layout
    compatibility; the facade stores them nibble-packed.
    """

    forest: forest_lib.HilbertForest
    quant: quantize.Quantizer
    codes_master: jax.Array  # (n, d) uint8, master-order layout
    sketches_master: jax.Array  # (n, Ws) uint32, master-order layout
    master_order: jax.Array  # (n,) int32: position -> point id
    master_rank: jax.Array  # (n,) int32: point id -> position

    @property
    def n_points(self) -> int:
        return self.master_order.shape[0]

    def memory_report(self) -> dict:
        """Bytes by component, mirroring the paper's RAM budget table."""
        return paper_memory_model(
            self.n_points,
            self.codes_master.shape[1],
            int(np.prod(self.sketches_master.shape)) * 4,
            self.forest.memory_bytes(),
        )


def hilbert_master_sort(points, cfg: ForestConfig, lo, hi):
    """Un-permuted Hilbert sort defining the master order (pure stage).

    Not jitted, so it shares the compiled sort of the forest's trees
    (:func:`repro.core.hilbert.hilbert_sort`)."""
    from repro.core import hilbert

    return hilbert.hilbert_sort(
        points, bits=cfg.bits, key_bits=cfg.key_bits, lo=lo, hi=hi
    )


def _merge_topk_dedup(best_pos, best_dist, new_pos, new_dist, k: int):
    """Merge candidate sets keyed by position; dedup; keep k smallest dists.

    Also the k-NN graph's per-order merge (keyed by point id).  Two stable
    sorts that carry their payload, so no gathers: a TPU runs
    ``take_along_axis`` as an element gather.  Same result as argsort +
    gathers + ``lax.top_k`` (whose ties also go to the lower index).
    """
    pos = jnp.concatenate([best_pos, new_pos], axis=1)
    dist = jnp.concatenate([best_dist, new_dist], axis=1)
    # Dedup: sort by position; equal-adjacent entries are duplicates (same
    # position ⇒ same sketch ⇒ same distance), mask all but the first.
    pos_s, dist_s = lax.sort((pos, dist), dimension=1, num_keys=1,
                             is_stable=True)
    dup = jnp.concatenate(
        [jnp.zeros_like(pos_s[:, :1], bool), pos_s[:, 1:] == pos_s[:, :-1]], axis=1
    )
    dist_s = jnp.where(dup, _INF, dist_s)
    dist_o, pos_o = lax.sort((dist_s, pos_s), dimension=1, num_keys=1,
                             is_stable=True)
    return pos_o[:, :k], dist_o[:, :k]


@functools.partial(
    jax.jit, static_argnames=("bits", "key_bits", "leaf_size", "k1", "k2",
                              "use_kernels")
)
def stage1_tree_merge(
    queries,
    qsketches,
    best_pos,
    best_dist,
    order,
    directory,
    lo,
    hi,
    perm,
    flip,
    master_rank,
    sketches_master,
    *,
    bits,
    key_bits,
    leaf_size,
    k1,
    k2,
    use_kernels=False,
):
    """One tree's stage-1: candidates → Hamming filter → merge into top-k2.

    Each step runs under a ``stage1.*`` named scope, which a device trace
    reports as the op's ``tf_op``.
    """
    with jax.named_scope("stage1.candidates"):
        cand_ids = forest_lib.tree_candidates(
            queries, order, directory, lo, hi, perm, flip,
            bits=bits, key_bits=key_bits, leaf_size=leaf_size, k1=k1,
        )  # (Q, k1)
    with jax.named_scope("stage1.sketch_gather"):
        mpos = master_rank[cand_ids]  # (Q, k1) master positions
        csk = sketches_master[mpos]  # (Q, k1, Ws)
    with jax.named_scope("stage1.hamming"):
        if use_kernels:
            from repro.kernels.hamming import hamming_rows

            hd = hamming_rows(qsketches, csk, use_kernel=True)  # (Q, k1)
        else:
            hd = sketch.hamming_distance(qsketches[:, None, :], csk)
    with jax.named_scope("stage1.merge"):
        return _merge_topk_dedup(best_pos, best_dist, mpos, hd, k2)


def _expand_windows(best_pos, n: int, h: int):
    """±h windows as (starts (Q, k2), pos (Q, k2, window), window size).

    Each surviving stage-1 position expands to a CONTIGUOUS window of
    ``window = min(2h+1, n)`` master-order rows starting at
    ``clip(best_pos - h, 0, n - window)`` — near the array edges the window
    shifts in-bounds instead of clamping to duplicate rows, so the candidate
    set is always a superset of the clamped expansion.  Contiguity is what
    lets candidate codes be read with windowed dynamic slices instead of a
    (Q, C, d) random gather.
    """
    window = min(2 * h + 1, n)
    starts = jnp.clip(best_pos - h, 0, n - window)  # (Q, k2)
    pos = starts[:, :, None] + jnp.arange(window, dtype=jnp.int32)[None, None, :]
    return starts, pos, window


def _window_slices(rows: jax.Array, starts: jax.Array, window: int) -> jax.Array:
    """Read (Q, k2) contiguous row windows: (n, W) -> (Q, k2, window, W)."""
    return jax.vmap(
        jax.vmap(lambda s: lax.dynamic_slice_in_dim(rows, s, window, axis=0))
    )(starts)


def _dedup_rank_topk(pos, d2, valid, master_order, k: int):
    """Sort by position, mask duplicates/invalid to +inf, final top-k.

    Shared tail of both stage-2 layouts: given identical (pos, d2, valid)
    inputs the outputs are identical, which is what makes the packed and
    unpacked search paths bit-identical on the XLA backend.

    The candidate pool is ``k2 * min(2h+1, n)``, which on a tiny index (or
    a tiny mutable segment queried with an inflated k) can be smaller than
    ``k``; the top-k is taken over the pool and the tail padded with
    id -1 / +inf — the same padding contract as ``brute_force_topk``.
    """
    sort_idx = jnp.argsort(pos, axis=1)
    pos_s = jnp.take_along_axis(pos, sort_idx, axis=1)
    d2_s = jnp.take_along_axis(d2, sort_idx, axis=1)
    valid_s = jnp.take_along_axis(valid, sort_idx, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros_like(pos_s[:, :1], bool), pos_s[:, 1:] == pos_s[:, :-1]], axis=1
    )
    d2_s = jnp.where((~dup) & valid_s, d2_s, jnp.inf)
    k_top = min(k, pos_s.shape[1])
    neg, idx = lax.top_k(-d2_s, k_top)
    final_pos = jnp.take_along_axis(pos_s, idx, axis=1)
    ids, dist = master_order[final_pos], -neg
    if k_top < k:
        qn, pad = ids.shape[0], k - k_top
        ids = jnp.concatenate(
            [ids, jnp.full((qn, pad), -1, ids.dtype)], axis=1
        )
        dist = jnp.concatenate(
            [dist, jnp.full((qn, pad), jnp.inf, dist.dtype)], axis=1
        )
    return ids, dist


@functools.partial(jax.jit, static_argnames=("h", "k"))
def stage2_expand_rank(
    queries, best_pos, codes_master, master_order, quant, *, h, k
):
    """±h expansion, dedup, exact ADC distance, top-k — UNPACKED codes.

    ``codes_master`` is (n, d) uint8.  Kept as the parity/benchmark
    reference for :func:`stage2_packed_windows`; both share the same
    windowed candidate expansion and dedup/top-k tail, so on the XLA
    backend their results are bit-identical (pack/unpack is lossless).
    """
    n = master_order.shape[0]
    qn, k2 = best_pos.shape
    starts, pos, window = _expand_windows(best_pos, n, h)
    codes = _window_slices(codes_master, starts, window)  # (Q, k2, window, d)
    codes = codes.reshape(qn, k2 * window, codes_master.shape[1])
    d2 = quantize.adc_distance(quant, queries, codes)  # (Q, C) fp32
    valid = jnp.broadcast_to((best_pos >= 0)[:, :, None], pos.shape)
    return _dedup_rank_topk(
        pos.reshape(qn, -1), d2, valid.reshape(qn, -1), master_order, k
    )


@functools.partial(jax.jit, static_argnames=("h", "k", "use_kernels"))
def stage2_packed_windows(
    queries, best_pos, codes_packed, master_order, quant, *, h, k,
    use_kernels=False,
):
    """Stage 2 on the RESIDENT nibble-packed codes (n, ceil(d/8)) uint32.

    Candidate codes are read as contiguous ±h windowed dynamic slices of
    the packed words (0.5 B/dim of traffic).  Distances route through
    ``repro.kernels.qdist.qdist_windows_from_packed``: the Pallas kernel
    when ``use_kernels`` (Mosaic on TPU, see ``repro.kernels.platform``),
    else a packed XLA path that unpacks losslessly — bit-identical to
    :func:`stage2_expand_rank` on the same candidates.  Its steps run under
    the named scopes ``stage2.windows``, ``stage2.adc`` and
    ``stage2.rank``.
    """
    n = master_order.shape[0]
    d = quant.centroids.shape[0]
    qn, k2 = best_pos.shape
    with jax.named_scope("stage2.windows"):
        starts, pos, window = _expand_windows(best_pos, n, h)
        win = _window_slices(codes_packed, starts, window)  # (Q, k2, window, W)
        win = win.reshape(qn, k2 * window, codes_packed.shape[1])
    with jax.named_scope("stage2.adc"):
        if use_kernels:
            from repro.kernels.qdist import qdist_windows_from_packed

            d2 = qdist_windows_from_packed(
                queries, win, quant.centroids, d=d, use_kernel=True,
            )
        else:
            d2 = quantize.adc_distance_packed(quant, queries, win, d=d)
    with jax.named_scope("stage2.rank"):
        valid = jnp.broadcast_to((best_pos >= 0)[:, :, None], pos.shape)
        return _dedup_rank_topk(
            pos.reshape(qn, -1), d2, valid.reshape(qn, -1), master_order, k
        )


@functools.partial(
    jax.jit,
    static_argnames=(
        "bits", "key_bits", "leaf_size", "k1", "k2", "h", "k", "use_kernels"
    ),
)
def fused_search_chunk(
    queries,
    orders,
    directories,
    lo,
    hi,
    perms,
    flips,
    master_rank,
    sketches_master,
    codes_packed,
    master_order,
    quant,
    *,
    bits,
    key_bits,
    leaf_size,
    k1,
    k2,
    h,
    k,
    use_kernels=False,
):
    """ONE dispatch per query chunk: sketch → scan over trees → packed stage 2.

    The per-tree Python loop becomes a ``lax.scan`` over the stacked forest
    arrays (``orders`` (T, n), ``directories`` (T, n_dir, W), ``perms``/
    ``flips`` (T, d)), so the stage-1 cost is one XLA dispatch regardless of
    ``n_trees``; query sketching and the packed windowed stage 2 fuse into
    the same computation.  Results are bit-identical to the per-tree loop +
    unpacked stage 2 (all stage-1 state is integer; stage 2 shares the same
    candidate expansion and, on XLA, the same lossless-unpack ADC).
    """
    qn = queries.shape[0]
    qsk = sketch.make_sketches(quant, queries)
    init = (
        jnp.full((qn, k2), -1, jnp.int32),
        jnp.full((qn, k2), _INF, jnp.int32),
    )

    def body(carry, tree):
        order, directory, perm, flip = tree
        best_pos, best_dist = stage1_tree_merge(
            queries, qsk, carry[0], carry[1],
            order, directory, lo, hi, perm, flip,
            master_rank, sketches_master,
            bits=bits, key_bits=key_bits, leaf_size=leaf_size, k1=k1, k2=k2,
            use_kernels=use_kernels,
        )
        return (best_pos, best_dist), None

    (best_pos, _), _ = lax.scan(body, init, (orders, directories, perms, flips))
    return stage2_packed_windows(
        queries, best_pos, codes_packed, master_order, quant,
        h=h, k=k, use_kernels=use_kernels,
    )


@functools.partial(jax.jit, static_argnames=("k",))
def merge_topk(ids, dists, *, k):
    """Associative cross-source top-k merge over (id, distance) candidates.

    The one merge shared by every fan-out search path: the sharded index
    merges per-shard top-k's (queries replicated, rows sharded), and the
    mutable index merges per-segment + write-buffer top-k's.  Top-k of a
    union is associative, so merging per-source top-k's is exact.

    Args:
      ids: (Q, C) int32 candidate ids; ``-1`` marks a padding slot.
      dists: (Q, C) float distances; non-finite entries are masked out.
      k: results per query (static).

    Returns:
      (ids (Q, k) int32, dists (Q, k)) sorted by ascending distance.

    Contract details, relied on by the call sites:
      * **Dedup by id**: the same id appearing in several sources (a point
        duplicated across shard boundaries as sentinel padding, or a stale
        row surviving mutable-index compaction) is kept once, at its
        SMALLEST distance; among equal distances the earliest input column
        wins.
      * **Column-stable tie order**: survivors keep their original column
        positions for the final ``lax.top_k``, so equal-distance results
        rank by input column order — a single already-sorted source passes
        through bit-identically (the mutable index's single-segment case).
      * **Padding**: when fewer than ``k`` finite candidates exist, the
        tail is id -1 / distance +inf — the same contract as
        :func:`brute_force_topk` and the stage-2 pipeline.

    Associativity is what makes *tree* reduction exact: merging
    per-source top-k's pairwise in any bracketing yields sorted
    distances bit-equal to one flat merge of the full pool (property-
    tested in ``tests/test_sharded.py``), which is the basis of the
    sharded facades' log2(S)-hop cross-shard merge
    (:func:`repro.core.distributed.cross_shard_merge_topk`).
    """
    qn, c = ids.shape
    # Locate duplicates without reordering: stable-lexsort each row by
    # (id primary, dist secondary), mark all but the first entry of every
    # equal-id run, and scatter the mask back to the original columns.
    order = jnp.lexsort((dists, ids), axis=1)
    ids_s = jnp.take_along_axis(ids, order, axis=1)
    dup_s = jnp.concatenate(
        [jnp.zeros_like(ids_s[:, :1], bool), ids_s[:, 1:] == ids_s[:, :-1]],
        axis=1,
    )
    rows = jnp.arange(qn, dtype=jnp.int32)[:, None]
    dup = jnp.zeros(ids.shape, bool).at[rows, order].set(dup_s)
    d = jnp.where(dup | (ids < 0) | ~jnp.isfinite(dists), jnp.inf, dists)
    k_top = min(k, c)
    neg, idx = lax.top_k(-d, k_top)
    out_ids = jnp.take_along_axis(ids, idx, axis=1)
    out_d = -neg
    out_ids = jnp.where(jnp.isfinite(out_d), out_ids, -1)
    if k_top < k:
        pad = k - k_top
        out_ids = jnp.concatenate(
            [out_ids, jnp.full((qn, pad), -1, out_ids.dtype)], axis=1
        )
        out_d = jnp.concatenate(
            [out_d, jnp.full((qn, pad), jnp.inf, out_d.dtype)], axis=1
        )
    return out_ids, out_d


def merge_topk_pair(ids_a, d_a, ids_b, d_b, first, *, k):
    """One hop of a pairwise :func:`merge_topk` tree reduction.

    Concatenates the two (Q, k) candidate sets and flat-merges them, with
    ``first`` — a traced boolean, broadcast over queries — choosing which
    source occupies the *leading* columns.  Column order is what breaks
    equal-distance ties in ``merge_topk``, so when two ranks of a
    butterfly exchange partial results and both call this with ``first``
    keyed to the lower rank, they merge identical column layouts and
    produce bit-identical outputs — the invariant that lets the sharded
    facades emit the reduction's result as a replicated array.

    Not jitted standalone: it is traced inside shard_map bodies (and the
    pure-host property test) where ``first`` is a per-rank scalar.
    """
    cat_i = jnp.where(
        first,
        jnp.concatenate([ids_a, ids_b], axis=1),
        jnp.concatenate([ids_b, ids_a], axis=1),
    )
    cat_d = jnp.where(
        first,
        jnp.concatenate([d_a, d_b], axis=1),
        jnp.concatenate([d_b, d_a], axis=1),
    )
    return merge_topk(cat_i, cat_d, k=k)


def inflate_k(k: int, dead: int, pool: int) -> int:
    """Tombstone-aware per-source ``k`` inflation (the LSM search contract).

    A sealed segment queried for ``k`` results can have up to ``dead`` of
    them masked by tombstones (or duplicate padding rows, on the sharded
    layout), so every fan-out search path asks each source for
    ``k + dead`` candidates, capped at the source's stage-2 candidate pool
    ``pool`` (beyond which inflation cannot help) and floored at 1.  Shared
    by :class:`repro.index.MutableHilbertIndex` (per segment) and
    :class:`repro.index.ShardedMutableHilbertIndex` (per generation,
    uniform across shards).
    """
    return max(1, min(k + dead, pool))


@functools.partial(jax.jit, static_argnames=("k",))
def brute_force_topk(queries, points, valid, *, k):
    """Exact squared-L2 top-k against a small point set (pure stage).

    The mutable index's write buffer is searched this way: ``points`` is the
    fixed-capacity buffer (so the jit cache is stable across fills) and
    ``valid`` masks dead / unfilled rows to +inf.  Uses the Gram expansion
    ||q-p||^2 = ||q||^2 - 2<q,p> + ||p||^2 so the transient is (Q, B), not
    (Q, B, d).  Returns (row indices into ``points`` (Q, k), d2 (Q, k));
    masked rows surface as d2 = +inf.

    The cross term runs at ``Precision.HIGHEST``: a TPU's default f32 matmul
    is a single bf16 pass, which would make these "exact" answers (and the
    post-compact bit-equality they feed) depend on the device.
    """
    qq = jnp.sum(queries * queries, axis=1)[:, None]
    pp = jnp.sum(points * points, axis=1)[None, :]
    cross = jnp.matmul(queries, points.T, precision=lax.Precision.HIGHEST)
    d2 = jnp.maximum(qq - 2.0 * cross + pp, 0.0)
    d2 = jnp.where(valid[None, :], d2, jnp.inf)
    neg, idx = lax.top_k(-d2, k)
    return idx, -neg


# ---------------------------------------------------------------------------
# Deprecation shims (one release): delegate to repro.index.HilbertIndex so
# old callers get bit-identical results from the same jitted stages.
# ---------------------------------------------------------------------------

# The legacy container keeps codes unpacked; the facade wants them packed.
# Cache the packed form per codes array so repeated legacy search() calls
# don't repack the whole database every time.  Keyed by id(); a weakref
# finalizer evicts the entry when the source array dies, so the id can
# never be reused against a stale entry and dropped legacy indexes don't
# pin database-sized arrays for the process lifetime.
_PACKED_SHIM_CACHE: dict = {}


def _packed_codes_cached(codes: jax.Array) -> jax.Array:
    import weakref

    key = id(codes)
    hit = _PACKED_SHIM_CACHE.get(key)
    if hit is None or hit[0]() is not codes:
        packed = quantize.pack_codes(codes)
        try:
            ref = weakref.ref(codes)
            weakref.finalize(codes, _PACKED_SHIM_CACHE.pop, key, None)
        except TypeError:  # not weakref-able: skip caching
            return packed
        _PACKED_SHIM_CACHE[key] = (ref, packed)
        hit = _PACKED_SHIM_CACHE[key]
    return hit[1]


def build_index(
    points: jax.Array,
    forest_cfg: ForestConfig,
    quant_cfg: QuantizerConfig = QuantizerConfig(),
) -> HilbertForestIndex:
    """DEPRECATED: use ``repro.index.HilbertIndex.build(points, cfg)``."""
    warnings.warn(
        "repro.core.search.build_index is deprecated; use "
        "repro.index.HilbertIndex.build(points, IndexConfig(...))",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro.index import HilbertIndex, IndexConfig

    idx = HilbertIndex.build(
        points,
        IndexConfig(forest=forest_cfg, quantizer=quant_cfg, store_points=False),
    )
    # The facade stores codes nibble-packed; the legacy container documents
    # the unpacked (n, d) uint8 layout, so unpack (lossless) on the way out.
    return HilbertForestIndex(
        forest=idx.forest,
        quant=idx.quant,
        codes_master=quantize.unpack_codes(idx.codes_master, idx.dim),
        sketches_master=idx.sketches_master,
        master_order=idx.master_order,
        master_rank=idx.master_rank,
    )


def search(
    index: HilbertForestIndex,
    queries: jax.Array,
    params: SearchParams,
    forest_cfg: ForestConfig,
    query_chunk: int = 2048,
    use_kernels: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """DEPRECATED: use ``repro.index.HilbertIndex.search(queries, params)``.

    This legacy entry point requires re-supplying the build-time
    ``forest_cfg``; a mismatch silently corrupts results.  The facade stores
    the config on the index and removes the argument entirely.
    """
    warnings.warn(
        "repro.core.search.search is deprecated; use "
        "repro.index.HilbertIndex.search(queries, params) — the index "
        "carries its own config",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro.index import HilbertIndex, IndexConfig

    idx = HilbertIndex(
        config=IndexConfig(
            forest=forest_cfg,
            quantizer=QuantizerConfig(bits=index.quant.bits),
            store_points=False,
        ),
        forest=index.forest,
        quant=index.quant,
        codes_master=_packed_codes_cached(index.codes_master),
        sketches_master=index.sketches_master,
        master_order=index.master_order,
        master_rank=index.master_rank,
        points=None,
    )
    return idx.search(
        queries,
        params,
        backend="pallas" if use_kernels else "xla",
        query_chunk=query_chunk,
    )
