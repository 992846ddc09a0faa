"""ShardedMutableHilbertIndex: shard-local LSM writes on the partitioned forest.

PR 2 made the index streaming (write buffer, sealed segments, tombstones,
compaction); PR 4 made it row-partitioned (``shard_map`` fused search with a
cross-shard ``merge_topk``).  This module composes the two so the sharded
layout — the only one that scales past one host — stops being static:

* **Per-shard write buffers** — every shard owns a fixed-capacity buffer
  slice; an insert is *routed* to the shard owning its master-curve range
  (:func:`repro.core.distributed.route_to_shards` against the partition's
  opening keys, frozen at build/compaction time), so freshly written rows
  keep the same curve locality the static partition has.  Before any bounds
  exist (an index born empty) routing falls back to round-robin.
* **Sealed generations** — when any shard's buffer fills (or
  :meth:`flush`), every shard's live buffered rows seal together into ONE
  cross-shard segment *generation*: per-shard :class:`HilbertIndex` builds
  sharing a generation-global quantizer (cross-shard distances within the
  generation are mutually comparable, exactly like the static sharded
  build), stacked ``(S, ...)`` and laid out ``P('data')``.  Shards pad to
  the generation's max row count with cyclic copies keeping REAL external
  ids; a shard with no rows holds copies of the generation's smallest-id
  row — duplicates collapse in the merge, no sentinels in the hot path.
* **Tombstones** — the dense by-external-id ``alive`` mask (the shared
  :class:`repro.index.mutable.LsmIdSpace`), device-resident padded to a
  power-of-two capacity so the search dispatch masks dead candidates
  in-computation (capacity growth retraces only log-many times).
* **Search** — ONE jitted dispatch per query chunk: inside ``shard_map``
  each device brute-forces its buffer slice and runs the PR 3 fused
  pipeline over every sealed generation — each generation's ``k`` inflated
  by its padding count plus a power-of-two bucket of its worst per-shard
  tombstone count (:func:`repro.core.search.inflate_k`), so dead or
  duplicate rows can never crowd a live neighbor out of the pool — maps
  local rows to external ids, masks tombstones, deflates the inflated
  pool to a local top-k and reduces across shards via
  :func:`repro.core.distributed.cross_shard_merge_topk` (butterfly tree
  by default, flat ``all_gather`` as the ``merge="gather"`` reference).
* **Compaction** — :meth:`compact` gathers the survivors in external-id
  (= insertion) order and literally calls
  :class:`repro.index.ShardedHilbertIndex`.build over them: the global
  Hilbert partition re-runs and rows RE-BALANCE across shards, so
  post-compact search is **bit-equal** to a fresh sharded build on the
  surviving rows (asserted under 8 virtual devices in
  ``tests/test_sharded_mutable.py``).  Tier merges between compactions stay
  shard-local: each shard re-sorts only its own rows, no cross-shard moves.

Checkpoints are **format_version 4** (see ``docs/CHECKPOINTS.md``): one
ordinary v2-valid bundle per (generation, shard) plus a buffer/tombstone
sidecar bundle, committed by a single atomically-renamed manifest.  v3
static-sharded checkpoints are adopted on load, and a mesh whose shard
count differs from the checkpoint's triggers a compact-on-load reshard.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import shutil
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import checkpoint
from repro.core import distributed as distributed_lib
from repro.core import quantize
from repro.core import search as search_lib
from repro.core.types import SearchParams
from repro.index.config import IndexConfig
from repro.obs.dispatch import dispatch_scope
from repro.obs.trace import span
from repro.index.facade import (
    BoundedJitCache,
    _pow2_bucket,
    build_with_timings,
    load_index_bundle,
    resolve_backend,
    save_index_bundle,
)
from repro.checkpoint import wal as wal_lib
from repro.index.mutable import (
    LsmIdSpace,
    WalFacade,
    _recover_wal,
    _restore_state_bundle,
)
from repro.testing.faults import fault_point
from repro.index.sharded import (
    ShardedHilbertIndex,
    ShardStack,
    shard_index_from_stack,
    stack_shard_indexes,
)

__all__ = [
    "ShardedMutableHilbertIndex",
    "ShardedSegment",
    "load_sharded_mutable_as_mutable",
    "load_sharded_mutable_bundle",
    "save_sharded_mutable_bundle",
]

_MANIFEST = "sharded_mutable_manifest.json"
_STATIC_MANIFEST = "sharded_manifest.json"  # v3 adoption
_SEG_SHARD_KIND = "sharded_mutable_segment_shard"
_DEFAULT_KIND = "sharded_mutable_hilbert_index"
_FORMAT_VERSION = 4
# Compiled search dispatches kept per index.  Keys change whenever the LSM
# shape does (generation sealed/merged, alive capacity doubled, tombstone
# bucket moved), so a long-lived streaming server would otherwise pin one
# shard_map executable per historical shape forever; the shared
# ``repro.index.facade.BoundedJitCache`` (LRU at this bound) caps that
# while keeping every shape the CURRENT state cycles through.
_CHUNK_FN_CACHE_MAX = 32


def _pow2_ceil(x: int) -> int:
    """0 for x<=0, else the smallest power of two >= x."""
    return 0 if x <= 0 else 1 << (int(x) - 1).bit_length()


@dataclasses.dataclass(eq=False)  # identity equality: segments hold arrays
class ShardedSegment:
    """One sealed cross-shard generation: stacked per-shard indexes + id map.

    ``stack.id_map`` (and its host copy ``ids_host``) maps each shard-local
    row — including cyclic padding rows — to its stable EXTERNAL id, so a
    local search hit resolves to a global result with one gather and
    duplicate padding rows collapse in the cross-shard merge.
    """

    stack: ShardStack            # (S, ...) leaves, P('data'); id_map = ext ids
    points: Optional[jax.Array]  # (S, n_pad, d) fp32, P('data'); None when
    #                              built with store_points=False (segment
    #                              serves but cannot merge/re-partition)
    quant: quantize.Quantizer    # generation-global, replicated
    gen: int                     # monotone generation tag (on-disk name)
    n_valid: np.ndarray          # (S,) owned-row counts (pre-padding)
    pad_max: int                 # max padding among shards that own rows
    ids_host: np.ndarray         # (S, n_pad) int32 ext ids incl. padding
    # worst-per-shard dead-count cache, keyed by the owner's delete epoch
    dead_cache: int = dataclasses.field(default=-1, repr=False)
    dead_epoch: int = dataclasses.field(default=-1, repr=False)

    @property
    def n_pad(self) -> int:
        return int(self.ids_host.shape[1])

    @property
    def n_owned(self) -> int:
        return int(self.n_valid.sum())


class ShardedMutableHilbertIndex(WalFacade):
    """Streaming insert/delete/search over a row-partitioned Hilbert forest.

    Typical lifecycle (requires a multi-device ``data`` mesh; on one device
    use :class:`repro.index.MutableHilbertIndex`)::

        idx = ShardedMutableHilbertIndex.build(points, IndexConfig(),
                                               mesh=data_mesh(8))
        ids = idx.insert(fresh)            # routed to curve-owning shards
        idx.delete(ids[:10])               # tombstoned, invisible to search
        hits, d2 = idx.search(queries, SearchParams(k=30))   # ONE dispatch
        idx.compact()                      # re-balance == fresh sharded build
        idx.save(path); idx = ShardedMutableHilbertIndex.load(path)

    ``insert`` may carry per-point ``values`` (e.g. kNN-LM next tokens);
    gather them for search hits with :meth:`values_at`.  External ids are
    stable for the life of the index, across flushes, compactions, and
    save/load.
    """

    def __init__(
        self,
        config: Optional[IndexConfig] = None,
        *,
        mesh: Optional[Mesh] = None,
        buffer_capacity: int = 1024,
        max_segments: int = 8,
    ):
        if buffer_capacity < 1:
            raise ValueError(f"buffer_capacity must be >= 1, got {buffer_capacity}")
        if max_segments < 1:
            raise ValueError(f"max_segments must be >= 1, got {max_segments}")
        # config.store_points is honored like the single-device mutable
        # index: True (the default) keeps raw fp32 points on every
        # generation so tier merges and the re-balancing compaction can
        # re-sort them; False reclaims that RAM for serving-only
        # deployments at the cost of maintenance (point-less generations
        # never merge; compact() raises).
        self.config = IndexConfig() if config is None else config
        if mesh is None:
            from repro.launch.mesh import data_mesh

            mesh = data_mesh(self.config.shards)
        self.mesh = mesh
        if self.n_shards < 2:
            raise ValueError(
                "ShardedMutableHilbertIndex needs a multi-device 'data' mesh; "
                "on one device use MutableHilbertIndex"
            )
        self.buffer_capacity = int(buffer_capacity)
        self.max_segments = int(max_segments)
        self.segments: List[ShardedSegment] = []
        self._lsm = LsmIdSpace()
        self._dim: Optional[int] = None
        self._buf_pts: Optional[np.ndarray] = None   # (S, B, d) fp32 host
        self._buf_ids: Optional[np.ndarray] = None   # (S, B) int32 host
        self._buf_count: Optional[np.ndarray] = None  # (S,) int
        self._dev_buf = None                         # device mirror, lazy
        self._perms: Optional[jax.Array] = None      # shared forest seed
        self._flips: Optional[jax.Array] = None
        self._bounds: Optional[np.ndarray] = None    # (S-1, W) curve keys
        self._route_lo: Optional[np.ndarray] = None  # (d,) partition box
        self._route_hi: Optional[np.ndarray] = None
        self._rr = 0                                 # round-robin cursor
        self._gen = 0
        self._alive_key = None
        self._alive_dev = None
        self._chunk_fns = BoundedJitCache(_CHUNK_FN_CACHE_MAX)
        self.last_dispatch_count = 0
        self._wal: Optional[wal_lib.WriteAheadLog] = None

    # -- introspection -------------------------------------------------------

    @property
    def n_shards(self) -> int:
        return int(self.mesh.shape["data"])

    @property
    def dim(self) -> Optional[int]:
        return self._dim

    @property
    def n_segments(self) -> int:
        return len(self.segments)

    @property
    def n_live(self) -> int:
        """Points visible to search (inserted, not deleted)."""
        return self._lsm.n_live

    @property
    def n_deleted(self) -> int:
        return self._lsm.n_deleted

    @property
    def n_buffered(self) -> int:
        """Live points still in the per-shard write buffers."""
        if self._buf_count is None:
            return 0
        total = 0
        for s in range(self.n_shards):
            c = int(self._buf_count[s])
            if c:
                total += int(np.count_nonzero(
                    self._lsm.alive[self._buf_ids[s, :c]]
                ))
        return total

    def memory_report(self) -> Dict[str, object]:
        """Bytes for ALL resident state, split sharded vs replicated.

        ``per_device_bytes`` ≈ ``sharded_bytes / n_shards +
        replicated_bytes`` — the number to compare against a per-device RAM
        budget, now including buffer slices and segment stacks on top of
        the static layout's accounting.
        """
        s = self.n_shards
        per_segment, sharded, replicated = [], 0, 0
        for seg in self.segments:
            leaves = list(seg.stack) + (
                [seg.points] if seg.points is not None else []
            )
            b = sum(int(leaf.nbytes) for leaf in leaves)
            per_segment.append(b)
            sharded += b
            replicated += sum(
                int(a.nbytes)
                for a in (seg.quant.boundaries, seg.quant.centroids)
            )
        if self._perms is not None:
            replicated += int(self._perms.nbytes) + int(self._flips.nbytes)
        # the device-resident tombstone mask is replicated on every device
        # at its pow2-padded search capacity (1 byte per slot)
        alive_dev_bytes = max(1024, _pow2_ceil(self._lsm.next_id))
        replicated += alive_dev_bytes
        buffer_bytes = 0
        if self._buf_pts is not None:
            buffer_bytes = self._buf_pts.nbytes + self._buf_ids.nbytes
        sharded += buffer_bytes
        rep: Dict[str, object] = {
            "n_shards": s,
            "segments_bytes": int(sum(per_segment)),
            "per_segment": [int(b) for b in per_segment],
            "buffer_bytes": int(buffer_bytes),
            "values_bytes": (
                0 if self._lsm.values is None else int(self._lsm.values.nbytes)
            ),
            "tombstone_bytes": int(self._lsm.alive.nbytes),
            "sharded_bytes": int(sharded),
            "replicated_bytes": int(replicated),
            "n_segments": self.n_segments,
            "n_live": self.n_live,
            "n_deleted": self.n_deleted,
            "n_buffered": self.n_buffered,
        }
        rep["total_bytes"] = (
            rep["sharded_bytes"] + rep["replicated_bytes"]
            + rep["values_bytes"] + rep["tombstone_bytes"]
        )
        rep["per_device_bytes"] = [sharded // s + replicated] * s
        return rep

    def __repr__(self) -> str:
        mb = self.memory_report()["total_bytes"] / 1e6
        return (
            f"ShardedMutableHilbertIndex(n_live={self.n_live}, "
            f"n_shards={self.n_shards}, n_segments={self.n_segments}, "
            f"buffered={self.n_buffered}/{self.n_shards}x"
            f"{self.buffer_capacity}, deleted={self.n_deleted}, "
            f"dim={self._dim}, {mb:.2f} MB)"
        )

    # -- construction --------------------------------------------------------

    @classmethod
    def build(
        cls,
        points: jax.Array,
        config: Optional[IndexConfig] = None,
        *,
        mesh: Optional[Mesh] = None,
        values: Optional[jax.Array] = None,
        buffer_capacity: int = 1024,
        max_segments: int = 8,
    ) -> "ShardedMutableHilbertIndex":
        """Build from an initial corpus: one balanced partitioned base.

        Args:
          points: (n, d) fp32 corpus; rows get external ids ``0..n-1``.
          config: build config.  ``store_points=True`` (the default) keeps
            raw points so tier merges and the re-balancing compaction can
            re-sort them; ``False`` serves RAM-lean but inserts route
            round-robin and maintenance raises.
          mesh: ``('data',)`` mesh; defaults to ``config.shards`` devices
            (else every local device).
          values: optional (n, ...) per-point payloads.
          buffer_capacity: write-buffer rows PER SHARD.
          max_segments: sealed-generation cap before tier merging.

        Returns:
          The streaming index; its initial search results are bit-equal to
          a static :class:`ShardedHilbertIndex` built from the same call.
        """
        base = ShardedHilbertIndex.build(points, config, mesh=mesh)
        return cls.from_sharded(
            base, values=values, buffer_capacity=buffer_capacity,
            max_segments=max_segments,
        )

    @classmethod
    def from_sharded(
        cls,
        base: ShardedHilbertIndex,
        *,
        values: Optional[jax.Array] = None,
        buffer_capacity: int = 1024,
        max_segments: int = 8,
    ) -> "ShardedMutableHilbertIndex":
        """Adopt a prebuilt static sharded index (external ids ``0..n-1``).

        The v3-checkpoint upgrade path: the static index's stack becomes
        generation 0 unchanged (its global row ids ARE the external ids),
        and the partition's opening keys are recovered from the stored
        points so future inserts route to the curve-owning shards.  A base
        built with ``store_points=False`` (the old static serving layout)
        still adopts: it serves and absorbs inserts/deletes, but inserts
        route round-robin (no points to recover bounds from) and
        maintenance touching generation 0 raises — matching
        :meth:`MutableHilbertIndex.from_index` semantics.
        """
        if base.single is not None:
            raise ValueError(
                "from_sharded needs a multi-shard index; wrap a 1-shard "
                "index with MutableHilbertIndex.from_index instead"
            )
        self = cls(
            config=base.config, mesh=base.mesh,
            buffer_capacity=buffer_capacity, max_segments=max_segments,
        )
        n = base.n_points
        vals = self._lsm.validate(n, values)
        self._dim = int(base.dim)
        self._alloc_buffers()
        self._lsm.register(n, vals)
        self._adopt_base(base, np.arange(n, dtype=np.int32))
        return self

    def _alloc_buffers(self) -> None:
        s = self.n_shards
        self._buf_pts = np.zeros(
            (s, self.buffer_capacity, self._dim), np.float32
        )
        self._buf_ids = np.full((s, self.buffer_capacity), -1, np.int32)
        self._buf_count = np.zeros((s,), np.int64)

    def _adopt_base(
        self, base: ShardedHilbertIndex, gids: np.ndarray
    ) -> None:
        """Wrap a fresh static build as a sealed generation + routing bounds.

        ``gids[row] = external id`` of the base corpus's row-th point.  The
        stack is reused as-is when the mapping is the identity (build/
        adopt); after a compaction it is the sorted live-id list.
        """
        id_host = np.asarray(jax.device_get(base.stack.id_map))
        ext_host = np.asarray(gids, np.int32)[id_host]
        stack = base.stack
        if not np.array_equal(ext_host, id_host):
            stack = stack._replace(id_map=jax.device_put(
                jnp.asarray(ext_host), NamedSharding(self.mesh, P("data"))
            ))
        self.segments.append(ShardedSegment(
            stack=stack, points=base.points, quant=base.quant,
            gen=self._gen, n_valid=np.asarray(base.n_valid, np.int64),
            pad_max=int(base.pad_max), ids_host=ext_host,
        ))
        self._gen += 1
        self._perms, self._flips = base.perms, base.flips
        if base.points is None:
            # No stored points to recover the partition's opening keys
            # from: inserts route round-robin until the next full build.
            self._bounds = None
            return
        # Recover the partition's opening keys for insert routing: shard
        # s's first owned row is its lowest point on the master curve.
        pts_host = np.asarray(jax.device_get(base.points))
        nv = [int(v) for v in base.n_valid]
        own = np.concatenate(
            [pts_host[s, : nv[s]] for s in range(self.n_shards) if nv[s]]
        )
        lo, hi = own.min(axis=0), own.max(axis=0)
        firsts = [
            pts_host[s, 0] if nv[s] else None for s in range(self.n_shards)
        ]
        self._bounds = distributed_lib.curve_partition_bounds(
            firsts, self.config.forest, lo, hi
        )
        self._route_lo, self._route_hi = lo, hi

    # -- mutation ------------------------------------------------------------

    def _register(self, points, values) -> Tuple[np.ndarray, np.ndarray]:
        """Shared insert bookkeeping (same contract as the mutable facade:
        ``prepare`` validates everything before any state mutates)."""
        pts, vals = self._lsm.prepare(points, values, self._dim)
        if pts.shape[0] == 0:
            return pts, np.zeros((0,), np.int32)
        if self._dim is None:
            self._dim = int(pts.shape[1])
            self._alloc_buffers()
        return pts, self._lsm.register(pts.shape[0], vals)

    def _route(self, pts: np.ndarray) -> np.ndarray:
        """Owning shard per row: curve bounds when known, else round-robin."""
        if self._bounds is None:
            out = (np.arange(pts.shape[0]) + self._rr) % self.n_shards
            self._rr = int((self._rr + pts.shape[0]) % self.n_shards)
            return out.astype(np.int32)
        return distributed_lib.route_to_shards(
            pts, self.config.forest, self._route_lo, self._route_hi,
            self._bounds,
        )

    def insert(
        self, points: jax.Array, values: Optional[jax.Array] = None
    ) -> np.ndarray:
        """Insert points (m, d); returns their stable external ids (m,).

        Each row lands in the write buffer of the shard owning its
        master-curve range (searchable immediately, exactly); whenever any
        shard's buffer fills, ALL shards' buffered rows seal into one
        cross-shard generation, and tier merging keeps the generation count
        at most ``max_segments``.  ``values`` attaches one payload per
        point — either every insert carries values or none does.
        """
        self._wal_log_insert("insert", points, values)
        pts, ids = self._register(points, values)
        m = pts.shape[0]
        if m == 0:
            return ids
        routes = self._route(pts)
        todo = np.ones((m,), np.bool_)
        while todo.any():
            for s in range(self.n_shards):
                idx = np.nonzero(todo & (routes == s))[0]
                if idx.size == 0:
                    continue
                c = int(self._buf_count[s])
                take = idx[: self.buffer_capacity - c]
                if take.size:
                    sl = slice(c, c + take.size)
                    self._buf_pts[s, sl] = pts[take]
                    self._buf_ids[s, sl] = ids[take]
                    self._buf_count[s] = c + take.size
                    todo[take] = False
            if int(self._buf_count.max()) >= self.buffer_capacity:
                self.flush()
        self._dev_buf = None
        self._maybe_merge_tiers()
        return ids

    def bulk_load(
        self, points: jax.Array, values: Optional[jax.Array] = None
    ) -> np.ndarray:
        """Seal a whole corpus at once, bypassing the write buffers.

        On an empty index this is :meth:`build`: a balanced partitioned
        base whose search is bit-equal to a fresh static sharded build.  On
        a live index the corpus seals as ONE generation, routed by the
        existing partition bounds.  Returns external ids like
        :meth:`insert`.
        """
        self._wal_log_insert("bulk_load", points, values)
        had_content = bool(self.segments) or self.n_buffered > 0
        pts, ids = self._register(points, values)
        if pts.shape[0] == 0:
            raise ValueError("bulk_load needs a non-empty (m, d) corpus")
        if not had_content:
            base = ShardedHilbertIndex.build(
                jnp.asarray(pts), self.config, mesh=self.mesh
            )
            self._adopt_base(base, ids)
            return ids
        routes = self._route(pts)
        self._seal([
            (ids[routes == s], pts[routes == s])
            for s in range(self.n_shards)
        ])
        self._maybe_merge_tiers()
        return ids

    def delete(self, ids) -> int:
        """Tombstone external ids; returns how many were newly deleted.

        Unknown ids raise ``KeyError``; repeats are idempotent.  Rows are
        physically dropped by the flush/merge/compaction that next touches
        their shard.
        """
        self._wal_log_delete(ids)
        return self._lsm.delete(ids)

    # -- generation lifecycle ------------------------------------------------

    def _seal(
        self, rows: Sequence[Tuple[np.ndarray, np.ndarray]],
        quant: Optional[quantize.Quantizer] = None,
        *, pad: bool = False,
    ) -> Optional[ShardedSegment]:
        """Seal per-shard (ids, points) rows into one stacked generation.

        Shards pad with cyclic copies of their own rows; a shard with no
        rows holds copies of the generation's smallest-id row, whose
        duplicate ids collapse in the cross-shard merge.  ``quant`` (fit
        over the union when not given) is shared by every shard so
        in-generation cross-shard distances are mutually comparable.

        With ``pad=True`` and ``config.seal_pow2`` the per-shard row count
        rounds up to the next power of two instead of the exact max, so
        steady-state churn recycles a handful of stack shapes and the
        jitted dispatch stops recompiling once warm.  The extra rows are
        more cyclic copies — ``pad_max`` grows, the existing per-
        generation k inflation absorbs them, results stay exact.
        """
        n_valid = np.asarray([ids.size for ids, _ in rows], np.int64)
        if int(n_valid.sum()) == 0:
            return None
        n_pad = int(n_valid.max())
        if pad and self.config.seal_pow2:
            n_pad = _pow2_ceil(max(n_pad, 1))
        all_ids = np.concatenate([ids for ids, _ in rows])
        all_pts = np.concatenate([pts for _, pts in rows])
        j = int(np.argmin(all_ids))
        e0, p0 = np.int32(all_ids[j]), all_pts[j]
        if quant is None:
            quant = quantize.fit(
                jnp.asarray(all_pts), bits=self.config.quantizer.bits,
                sample_limit=self.config.quantizer.sample_limit,
            )
        shard_indexes, id_maps = [], np.zeros(
            (self.n_shards, n_pad), np.int32
        )
        for s, (ids_s, pts_s) in enumerate(rows):
            if ids_s.size == 0:
                id_maps[s] = np.full((n_pad,), e0, np.int32)
                pts_pad = np.tile(p0[None, :], (n_pad, 1))
            else:
                reps = -(-n_pad // ids_s.size)
                id_maps[s] = np.tile(
                    ids_s.astype(np.int32), reps
                )[:n_pad]
                pts_pad = np.tile(pts_s, (reps, 1))[:n_pad]
            with span("lsm.generation_build",
                      rows=int(pts_pad.shape[0]), shard=s), \
                    dispatch_scope("lsm.generation_build"):
                idx, _ = build_with_timings(
                    jnp.asarray(pts_pad), self.config, quant=quant
                )
            shard_indexes.append(idx)
        stack, points = stack_shard_indexes(
            self.mesh, shard_indexes, id_maps,
            store_points=self.config.store_points,
        )
        repl = NamedSharding(self.mesh, P())
        seg = ShardedSegment(
            stack=stack, points=points,
            quant=jax.device_put(quant, repl),
            gen=self._gen, n_valid=n_valid,
            pad_max=int(max(
                (n_pad - int(v) for v in n_valid if v > 0), default=0
            )),
            ids_host=id_maps,
        )
        self._gen += 1
        if self._perms is None:
            self._perms = jax.device_put(shard_indexes[0].forest.perms, repl)
            self._flips = jax.device_put(shard_indexes[0].forest.flips, repl)
        self.segments.append(seg)
        return seg

    def flush(self) -> Optional[ShardedSegment]:
        """Seal every shard's live buffered rows into one generation.

        Dead buffer rows drop here for good.  No-op (returns None) when all
        buffers are empty or fully tombstoned.
        """
        if self._buf_count is None or int(self._buf_count.sum()) == 0:
            return None
        rows = []
        for s in range(self.n_shards):
            c = int(self._buf_count[s])
            ids_s = self._buf_ids[s, :c]
            live = self._lsm.alive[ids_s]
            rows.append((ids_s[live].copy(), self._buf_pts[s, :c][live].copy()))
        self._buf_count[:] = 0
        self._buf_ids[:] = -1
        self._dev_buf = None
        return self._seal(rows, pad=True)

    def _owned_rows(
        self, seg: ShardedSegment, s: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Shard s's owned (pre-padding) external ids + points, host-side."""
        if seg.points is None:
            raise ValueError(
                "cannot re-sort a generation built without stored points "
                "(IndexConfig(store_points=False), or a store_points=False "
                "index adopted via from_sharded)"
            )
        nv = int(seg.n_valid[s])
        ids = seg.ids_host[s, :nv]
        pts = np.asarray(jax.device_get(seg.points[s]))[:nv]
        return ids, pts

    def _merge_segments(
        self, to_merge: Sequence[ShardedSegment]
    ) -> Optional[ShardedSegment]:
        """Replace ``to_merge`` with one generation; tombstoned rows vanish.

        Shard-local by construction: each shard's new rows are the union of
        its own rows across the merged generations (re-sorted by external
        id), so tier merges never move rows between shards — only
        :meth:`compact` re-runs the global partition.
        """
        rows = []
        for s in range(self.n_shards):
            owned = [self._owned_rows(seg, s) for seg in to_merge]
            ids_s = np.concatenate([ids for ids, _ in owned])
            pts_s = np.concatenate([pts for _, pts in owned])
            live = self._lsm.alive[ids_s]
            ids_s, pts_s = ids_s[live], pts_s[live]
            order = np.argsort(ids_s, kind="stable")
            rows.append((ids_s[order], pts_s[order]))
        self.segments = [x for x in self.segments if x not in to_merge]
        return self._seal(rows, pad=True)

    def _maybe_merge_tiers(self) -> None:
        while len(self.segments) > self.max_segments:
            # Only generations holding raw points can be re-sorted; without
            # store_points the generation count is unbounded by design.
            mergeable = [g for g in self.segments if g.points is not None]
            if len(mergeable) < 2:
                return
            smallest = sorted(mergeable, key=lambda g: g.n_owned)[:2]
            self._merge_segments(smallest)

    def compact(self) -> "ShardedMutableHilbertIndex":
        """Full compaction: re-partition and re-balance the survivors.

        Gathers every live row (segments + buffers) in external-id
        (= insertion) order and rebuilds via
        :class:`ShardedHilbertIndex`.build — ``hilbert_partition`` re-runs,
        so rows re-balance across shards and post-compact search is
        bit-equal to a fresh sharded build over the surviving points.
        Raises if any generation was built without stored points
        (``store_points=False``) — there is nothing to re-sort.  Returns
        self (chainable).
        """
        ids, pts = self._gather_live()
        if self._buf_count is not None:
            self._buf_count[:] = 0
            self._buf_ids[:] = -1
        self._dev_buf = None
        self.segments = []
        self._chunk_fns.clear()
        if ids.size == 0:
            self._bounds = None
            return self
        with span("lsm.compact", rows=int(ids.size)), \
                dispatch_scope("lsm.compact"):
            base = ShardedHilbertIndex.build(
                jnp.asarray(pts), self.config, mesh=self.mesh
            )
            self._adopt_base(base, ids)
        return self

    # -- serving-engine hooks ------------------------------------------------

    def snapshot(self) -> "ShardedMutableHilbertIndex":
        """Cheap shared-buffer copy for off-path maintenance (double-buffer).

        Mirrors :meth:`MutableHilbertIndex.snapshot`: sealed generations
        are immutable, so their stacked device arrays are SHARED (zero
        copy) under fresh :class:`ShardedSegment` wrappers (dead-count
        caches must not race between serving copy and shadow); the
        per-shard write buffers, routing bounds, and LSM bookkeeping are
        deep-copied.  The compiled-dispatch cache starts empty on the
        snapshot — the executables are keyed by LSM shape and re-resolve on
        first search after a swap.  The WAL is deliberately NOT carried
        over: the shadow must not re-log replayed mutations; the engine
        transfers the log old→shadow at swap time.
        """
        snap = ShardedMutableHilbertIndex(
            config=self.config, mesh=self.mesh,
            buffer_capacity=self.buffer_capacity,
            max_segments=self.max_segments,
        )
        snap._dim = self._dim
        if self._buf_pts is not None:
            snap._buf_pts = self._buf_pts.copy()
            snap._buf_ids = self._buf_ids.copy()
            snap._buf_count = self._buf_count.copy()
        snap._lsm = self._lsm.clone()
        snap._gen = self._gen
        snap._perms, snap._flips = self._perms, self._flips
        snap._rr = self._rr
        if self._bounds is not None:
            snap._bounds = self._bounds.copy()
            snap._route_lo = np.asarray(self._route_lo).copy()
            snap._route_hi = np.asarray(self._route_hi).copy()
        snap.segments = [
            ShardedSegment(
                stack=seg.stack, points=seg.points, quant=seg.quant,
                gen=seg.gen, n_valid=seg.n_valid.copy(),
                pad_max=seg.pad_max, ids_host=seg.ids_host,
            )
            for seg in self.segments
        ]
        return snap

    def maintenance_stats(self) -> Dict[str, object]:
        """The trigger signals a background maintainer watches (host-only)."""
        next_id = max(self._lsm.next_id, 1)
        return {
            "n_segments": self.n_segments,
            "mergeable_segments": sum(
                1 for g in self.segments if g.points is not None
            ),
            "n_live": self.n_live,
            "n_deleted": self.n_deleted,
            "n_buffered": self.n_buffered,
            "tombstone_ratio": float(self.n_deleted) / float(next_id),
        }

    def _gather_live(self) -> Tuple[np.ndarray, np.ndarray]:
        """All live (ids, points), host-side, sorted by external id."""
        parts_i, parts_p = [], []
        for seg in self.segments:
            for s in range(self.n_shards):
                ids_s, pts_s = self._owned_rows(seg, s)
                parts_i.append(ids_s)
                parts_p.append(pts_s)
        if self._buf_count is not None:
            for s in range(self.n_shards):
                c = int(self._buf_count[s])
                parts_i.append(self._buf_ids[s, :c])
                parts_p.append(self._buf_pts[s, :c])
        if not parts_i:
            d = self._dim or 0
            return np.zeros((0,), np.int32), np.zeros((0, d), np.float32)
        ids = np.concatenate(parts_i)
        pts = np.concatenate(parts_p)
        live = self._lsm.alive[ids]
        ids, pts = ids[live], pts[live]
        order = np.argsort(ids, kind="stable")
        return ids[order].astype(np.int32), np.ascontiguousarray(pts[order])

    # -- search --------------------------------------------------------------

    def _segment_dead_max(self, seg: ShardedSegment) -> int:
        """Worst per-shard tombstone count (padding dups included), cached.

        Safe under the engine's SHARED read lock: deletes hold the write
        side, so the epoch cannot move mid-read; racing readers perform
        an identical idempotent fill (value written before the epoch
        stamp, so a fresh epoch always pairs with a fresh count).
        """
        if seg.dead_epoch != self._lsm.delete_epoch:
            alive = self._lsm.alive
            seg.dead_cache = max(
                seg.n_pad - int(np.count_nonzero(alive[seg.ids_host[s]]))
                for s in range(self.n_shards)
            )
            seg.dead_epoch = self._lsm.delete_epoch
        return seg.dead_cache

    def rewrite_pressure(self, params: Optional[SearchParams] = None) -> int:
        """Generations whose tombstones exceed their stage-2 candidate
        pool under ``params`` — the read-triggered-rewrite condition,
        surfaced as a maintenance trigger for engines that search with
        ``allow_rewrite=False`` (shared read lock: the read path must
        not rebuild segments).  Mirrors the single-device facade.
        """
        if params is None:
            params = SearchParams()
        n = 0
        for seg in list(self.segments):
            cap = params.k2 * min(2 * params.h + 1, seg.n_pad)
            if (self._segment_dead_max(seg) > max(cap - params.k, 0)
                    and seg.points is not None):
                n += 1
        return n

    def _alive_device(self) -> Tuple[int, jax.Array]:
        """The alive mask padded to a pow2 capacity, replicated on device.

        Lock-free-safe lazy mirror: invalidation happens only in
        write-exclusive mutators (the key embeds the delete epoch and id
        cursor), concurrent readers may at worst both ``device_put`` the
        SAME mask (the loser's array is dropped), and the value is
        published before the key so a reader that observes a fresh key
        never pairs it with a stale array.  Readers work off locals —
        ``self`` is re-read once, not per use.
        """
        cap = max(1024, _pow2_ceil(self._lsm.next_id))
        key = (cap, self._lsm.delete_epoch, self._lsm.next_id)
        dev = self._alive_dev
        if self._alive_key != key or dev is None:
            pad = np.zeros((cap,), np.bool_)
            pad[: self._lsm.next_id] = self._lsm.alive
            dev = jax.device_put(
                jnp.asarray(pad), NamedSharding(self.mesh, P())
            )
            self._alive_dev = dev   # value BEFORE key: see docstring
            self._alive_key = key
        return cap, dev

    def _device_buffers(self) -> Tuple[jax.Array, jax.Array]:
        # same lazy-mirror discipline as _alive_device: read into a local,
        # fill idempotently; writers invalidate by assigning None under
        # the engine's exclusive lock
        buf = self._dev_buf
        if buf is None:
            data_sh = NamedSharding(self.mesh, P("data"))
            buf = (
                jax.device_put(jnp.asarray(self._buf_pts), data_sh),
                jax.device_put(jnp.asarray(self._buf_ids), data_sh),
            )
            self._dev_buf = buf
        return buf

    def search(
        self,
        queries: jax.Array,
        params: SearchParams = SearchParams(),
        *,
        backend: str = "auto",
        query_chunk: Optional[int] = None,
        merge: Optional[str] = None,
        prune: Optional[bool] = None,
        allow_rewrite: bool = True,
    ) -> Tuple[jax.Array, jax.Array]:
        """Mesh-wide streaming search; returns (ext ids (Q, k), sq-dists).

        ONE jitted dispatch per query chunk (``last_dispatch_count`` records
        the count): inside ``shard_map`` every device runs the fused
        pipeline over each sealed generation plus a brute-force pass over
        its buffer slice, masks tombstones against the device-resident
        alive mask, deflates the concatenated per-shard pool to a local
        top-k, and the shards reduce via
        :func:`repro.core.distributed.cross_shard_merge_topk` — the same
        ``merge="auto"|"gather"|"tree"`` / ``prune`` knobs as
        :class:`ShardedHilbertIndex` (defaults from the config).  When
        fewer than ``k`` live points exist the tail is id -1 / +inf.

        A generation tombstoned past its stage-2 candidate pool is
        rewritten on the spot (read-triggered shard-local compaction),
        mirroring the single-device mutable index.  ``allow_rewrite=False``
        suppresses that rewrite (the serving engine's shared-read-lock
        path: see :meth:`rewrite_pressure`) at the cost of degraded
        recall on the over-tombstoned generation until maintenance
        compacts it.
        """
        if params is None:
            params = SearchParams()
        merge = distributed_lib.resolve_merge(
            merge if merge is not None else self.config.merge, self.n_shards
        )
        prune = self.config.merge_prune if prune is None else bool(prune)
        use_kernels = resolve_backend(backend) == "pallas"
        if query_chunk is None:
            query_chunk = self.config.query_chunk
        q = jnp.asarray(queries)
        qn, k = q.shape[0], params.k
        dispatches = 0
        self.last_dispatch_count = 0
        if qn == 0 or self._dim is None or (
            not self.segments and self.n_buffered == 0
        ):
            return (
                jnp.full((qn, k), -1, jnp.int32),
                jnp.full((qn, k), jnp.inf, jnp.float32),
            )
        # Read-triggered rewrite: a generation whose tombstones could crowd
        # live neighbors out of its candidate pool is rebuilt (shard-local,
        # dead rows dropped for good) before this search runs.  Suppressed
        # on the engine's shared-read-lock path (allow_rewrite=False).
        if allow_rewrite:
            for seg in list(self.segments):
                cap = params.k2 * min(2 * params.h + 1, seg.n_pad)
                if (self._segment_dead_max(seg) > max(cap - k, 0)
                        and seg.points is not None):
                    self._merge_segments([seg])
        # Per-generation k inflation: padding dups + a pow2 bucket of the
        # worst tombstone count (bucketed so deletes only retrace the
        # dispatch log-many times).
        seg_meta = []
        for seg in self.segments:
            cap = params.k2 * min(2 * params.h + 1, seg.n_pad)
            k_seg = search_lib.inflate_k(
                k, seg.pad_max + _pow2_ceil(self._segment_dead_max(seg)), cap
            )
            seg_meta.append((seg.n_pad, k_seg))
        alive_cap, alive = self._alive_device()
        bpts, bids = self._device_buffers()
        fn = self._chunk_fn(
            params, tuple(seg_meta), use_kernels, alive_cap, merge, prune
        )
        stacks = tuple(seg.stack for seg in self.segments)
        quants = tuple(seg.quant for seg in self.segments)
        repl = NamedSharding(self.mesh, P())
        perms = (
            self._perms if self._perms is not None
            else jax.device_put(jnp.zeros((1, self._dim), jnp.int32), repl)
        )
        flips = (
            self._flips if self._flips is not None
            else jax.device_put(jnp.zeros((1, self._dim), jnp.bool_), repl)
        )
        outs_i, outs_d = [], []
        for s in range(0, qn, query_chunk):
            chunk = q[s : s + query_chunk]
            m = chunk.shape[0]
            bucket = _pow2_bucket(m, query_chunk)
            if bucket > m:
                chunk = jnp.pad(chunk, ((0, bucket - m), (0, 0)))
            with dispatch_scope("sharded_mutable.search"):
                ids, dists = fn(chunk, stacks, quants, perms, flips, bpts,
                                bids, alive)
            dispatches += 1
            if bucket > m:
                ids, dists = ids[:m], dists[:m]
            outs_i.append(ids)
            outs_d.append(dists)
        # one assignment at the end: last_dispatch_count is a diagnostic
        # scalar, and concurrent readers should each publish a consistent
        # per-call count rather than interleave increments
        self.last_dispatch_count = dispatches
        return jnp.concatenate(outs_i), jnp.concatenate(outs_d)

    def _chunk_fn(self, params: SearchParams, seg_meta: tuple,
                  use_kernels: bool, alive_cap: int, merge: str, prune: bool):
        key = (params.k1, params.k2, params.h, params.k, seg_meta,
               use_kernels, alive_cap, self.buffer_capacity, merge, prune)
        fn = self._chunk_fns.get(key)
        if fn is not None:
            return fn
        mesh = self.mesh
        fcfg = self.config.forest
        k1, k2, h, k = params.k1, params.k2, params.h, params.k
        k_buf = max(1, min(k, self.buffer_capacity))
        k_segs = [m[1] for m in seg_meta]
        n_shards = self.n_shards

        def shard_fn(q, stacks, quants, perms, flips, bpts, bids, alive):
            # shard_map keeps every sharded leading axis at local size 1.
            parts_g, parts_d = [], []
            for st, quant, k_seg in zip(stacks, quants, k_segs):
                ids_l, d2 = search_lib.fused_search_chunk(
                    q, st.orders[0], st.directories[0], st.lo[0], st.hi[0],
                    perms, flips, st.master_rank[0], st.sketches[0],
                    st.codes[0], st.master_order[0], quant,
                    bits=fcfg.bits, key_bits=fcfg.key_bits,
                    leaf_size=fcfg.leaf_size, k1=k1, k2=k2, h=h, k=k_seg,
                    use_kernels=use_kernels,
                )
                gids = jnp.where(
                    ids_l >= 0, st.id_map[0][jnp.maximum(ids_l, 0)], -1
                )
                live = (gids >= 0) & alive[
                    jnp.clip(gids, 0, alive.shape[0] - 1)
                ]
                parts_g.append(jnp.where(live, gids, -1))
                parts_d.append(jnp.where(live, d2, jnp.inf))
            bvalid = (bids[0] >= 0) & alive[
                jnp.clip(bids[0], 0, alive.shape[0] - 1)
            ]
            bidx, bd2 = search_lib.brute_force_topk(
                q, bpts[0], bvalid, k=k_buf
            )
            parts_g.append(jnp.where(jnp.isfinite(bd2), bids[0][bidx], -1))
            parts_d.append(bd2)
            cg = jnp.concatenate(parts_g, axis=1)
            cd = jnp.concatenate(parts_d, axis=1)
            return distributed_lib.cross_shard_merge_topk(
                cg, cd, k=k, axis="data", axis_size=n_shards,
                merge=merge, prune=prune,
            )

        fn = jax.jit(
            shard_map(
                shard_fn,
                mesh=mesh,
                in_specs=(P(None, None), P("data"), P(), P(), P(),
                          P("data"), P("data"), P()),
                out_specs=(P(None, None), P(None, None)),
                check_vma=False,
            )
        )
        self._chunk_fns.put(key, fn)
        return fn

    # -- values --------------------------------------------------------------

    def values_at(self, ids, fill=0) -> jax.Array:
        """Gather per-point values for search-result ids; -1 slots get fill."""
        return self._lsm.values_at(ids, fill=fill)

    def values_dense(self) -> jax.Array:
        """The dense by-external-id values array (stale rows where deleted)."""
        return self._lsm.values_dense()

    # -- persistence ---------------------------------------------------------

    def save(self, path: str, *, kind: str = _DEFAULT_KIND,
             extra_meta: Optional[Dict] = None) -> str:
        return save_sharded_mutable_bundle(
            self, path, kind=kind, extra_meta=extra_meta
        )

    @classmethod
    def load(
        cls, path: str, *, mesh: Optional[Mesh] = None,
        kind: str = _DEFAULT_KIND,
    ) -> "ShardedMutableHilbertIndex":
        index, _ = load_sharded_mutable_bundle(path, mesh=mesh, kind=kind)
        return index


def _seg_shard_uid(seg: ShardedSegment, s: int) -> str:
    """Content address of one (generation, shard) bundle for save dedup."""
    h = hashlib.sha1()
    h.update(np.int64(seg.gen).tobytes())
    codes = np.asarray(jax.device_get(seg.stack.codes[s]))
    h.update(np.asarray(
        seg.ids_host[s].shape + codes.shape, np.int64
    ).tobytes())
    h.update(seg.ids_host[s].tobytes())
    h.update(codes.tobytes())
    return h.hexdigest()


def _shard_bundle_uid(seg_dir: str) -> Optional[str]:
    step = checkpoint.latest_step(seg_dir)
    if step is None:
        return None
    try:
        with open(os.path.join(seg_dir, f"step_{step:08d}",
                               "manifest.json")) as f:
            return json.load(f).get("extra", {}).get("segment_uid")
    except (OSError, ValueError):
        return None


def save_sharded_mutable_bundle(
    index: ShardedMutableHilbertIndex,
    path: str,
    *,
    kind: str = _DEFAULT_KIND,
    extra_meta: Optional[Dict] = None,
) -> str:
    """Persist as per-(generation, shard) bundles + sidecar + one manifest.

    Format_version 4: every piece is an atomic ``repro.checkpoint`` bundle
    — one ordinary v2-valid index bundle per (generation, shard), written
    only when its content uid differs from what is on disk, plus a
    buffer/tombstone/values/bounds sidecar at a FRESH step — and the
    top-level JSON manifest renames into place LAST.  A crash mid-save or a
    concurrent load always observes a complete, mutually consistent set;
    bundles referenced by neither the new nor the previous manifest are
    pruned after the commit (one generation of grace).
    """
    os.makedirs(path, exist_ok=True)
    prev_manifest = {}
    try:
        with open(os.path.join(path, _MANIFEST)) as f:
            prev_manifest = json.load(f)
    except (OSError, ValueError):
        pass
    s_count = index.n_shards
    seg_entries = []
    for seg in index.segments:
        name = f"gen_{seg.gen:06d}"
        for s in range(s_count):
            shard_dir = os.path.join(path, "segments", name, f"shard_{s:05d}")
            uid = _seg_shard_uid(seg, s)
            if _shard_bundle_uid(shard_dir) != uid:
                shard_index = shard_index_from_stack(
                    index.config, seg.stack, seg.points, seg.quant,
                    index._perms, index._flips, s,
                )
                save_index_bundle(
                    shard_index, shard_dir, kind=_SEG_SHARD_KIND,
                    extra_arrays={"ids": jnp.asarray(seg.ids_host[s])},
                    extra_meta={
                        "shard": s, "n_shards": s_count,
                        "n_valid": int(seg.n_valid[s]),
                        "segment_uid": uid,
                    },
                )
        seg_entries.append({
            "name": name,
            "gen": int(seg.gen),
            "pad_max": int(seg.pad_max),
            "n_valid": [int(v) for v in seg.n_valid],
        })
    # Sidecar: occupied buffer rows (+ shard assignment), tombstones,
    # values, routing bounds — everything the stacked bundles don't carry.
    # Tombstoned buffer rows are KEPT: load() must reconstruct the exact
    # in-memory slot layout so WAL replay crosses the same flush
    # boundaries the live process did (the bit-equal-recovery invariant).
    state: Dict[str, np.ndarray] = {"alive": index._lsm.alive}
    if index._lsm.values is not None:
        state["values"] = index._lsm.values
    d = index._dim if index._dim is not None else 0
    bsh, bid, bpt = [], [], []
    if index._buf_count is not None:
        for s in range(s_count):
            c = int(index._buf_count[s])
            bsh.append(np.full((c,), s, np.int32))
            bid.append(index._buf_ids[s, :c].copy())
            bpt.append(index._buf_pts[s, :c].copy())
    state["buffer_shard"] = (
        np.concatenate(bsh) if bsh else np.zeros((0,), np.int32)
    )
    state["buffer_ids"] = (
        np.concatenate(bid) if bid else np.zeros((0,), np.int32)
    )
    state["buffer_points"] = (
        np.concatenate(bpt) if bpt else np.zeros((0, d), np.float32)
    )
    if index._bounds is not None:
        state["bounds"] = index._bounds
        state["route_lo"] = np.asarray(index._route_lo, np.float32)
        state["route_hi"] = np.asarray(index._route_hi, np.float32)
    state_dir = os.path.join(path, "state")
    state_step = (checkpoint.latest_step(state_dir) or 0) + 1
    checkpoint.save(state_dir, step=state_step, tree=state, extra={})
    manifest = {
        "kind": kind,
        "format_version": _FORMAT_VERSION,
        "config": index.config.to_dict(),
        "n_shards": s_count,
        "buffer_capacity": index.buffer_capacity,
        "max_segments": index.max_segments,
        "next_id": int(index._lsm.next_id),
        "gen": int(index._gen),
        "dim": index._dim,
        "track_values": index._lsm.track_values,
        "has_bounds": index._bounds is not None,
        "state_step": state_step,
        "segments": seg_entries,
        "extra_meta": extra_meta or {},
    }
    fault_point(
        "sharded_mutable.save.pre_manifest",
        path=os.path.join(path, _MANIFEST),
    )
    checkpoint.atomic_write_json(os.path.join(path, _MANIFEST), manifest)
    keep = {e["name"] for e in manifest["segments"]} | {
        e["name"] for e in prev_manifest.get("segments", [])
    }
    seg_root = os.path.join(path, "segments")
    if os.path.isdir(seg_root):
        for name in os.listdir(seg_root):
            if name.startswith("gen_") and name not in keep:
                shutil.rmtree(os.path.join(seg_root, name),
                              ignore_errors=True)
    checkpoint.prune_steps(
        state_dir, {state_step, prev_manifest.get("state_step")}
    )
    # The manifest is the commit point: every record logged before it is
    # now covered by the checkpoint.  A crash in between just replays the
    # covered tail as no-ops (next_id watermark).
    if index._wal is not None:
        index._wal.truncate()
    return path


def load_sharded_mutable_bundle(
    path: str, *, mesh: Optional[Mesh] = None, kind: str = _DEFAULT_KIND
) -> Tuple[ShardedMutableHilbertIndex, Dict]:
    """Inverse of :func:`save_sharded_mutable_bundle`; returns (index, meta).

    Same-shard-count loads are array-identical round-trips.  A mesh whose
    ``data`` axis differs from the checkpoint's shard count triggers a
    compact-on-load RESHARD (live rows gathered, partition rebuilt at the
    new count, buffered rows folded in).  A directory holding a v3 static
    sharded checkpoint (no v4 manifest) is adopted via
    :meth:`ShardedMutableHilbertIndex.from_sharded` — the format-upgrade
    path.
    """
    if mesh is None:
        from repro.launch.mesh import data_mesh

        mesh = data_mesh()
    mpath = os.path.join(path, _MANIFEST)
    if not os.path.exists(mpath):
        if not os.path.exists(os.path.join(path, _STATIC_MANIFEST)):
            raise FileNotFoundError(
                f"no sharded-mutable (v4) or sharded (v3) manifest under "
                f"{path!r}"
            )
        base = ShardedHilbertIndex.load(path, mesh=mesh)
        index = ShardedMutableHilbertIndex.from_sharded(base)
        _recover_wal(index, path)
        return index, {}
    with open(mpath) as f:
        manifest = json.load(f)
    if manifest.get("kind") != kind:
        raise ValueError(
            f"{path!r} is not a sharded-mutable checkpoint of kind {kind!r} "
            f"(kind={manifest.get('kind')!r})"
        )
    config = IndexConfig.from_dict(manifest["config"])
    target = int(mesh.shape["data"])
    saved = int(manifest["n_shards"])
    state = _restore_state_bundle(
        os.path.join(path, "state"), manifest.get("state_step")
    )

    if target != saved:
        # Compact-on-load reshard: gather live rows, rebuild at the new
        # count (buffered rows fold into the rebuilt base).
        if target == 1:
            raise ValueError(
                "cannot load a sharded-mutable checkpoint onto a 1-device "
                "mesh as ShardedMutableHilbertIndex; use "
                "load_sharded_mutable_as_mutable for the single-device "
                "mutable layout"
            )
        ids, pts = _gather_live_v4(path, manifest, state)
        index = ShardedMutableHilbertIndex(
            config=dataclasses.replace(config, shards=None), mesh=mesh,
            buffer_capacity=int(manifest["buffer_capacity"]),
            max_segments=int(manifest["max_segments"]),
        )
        _restore_lsm(index, manifest, state)
        index._gen = int(manifest["gen"])
        if manifest.get("dim") is not None:
            index._dim = int(manifest["dim"])
            index._alloc_buffers()
        if ids.size:
            base = ShardedHilbertIndex.build(
                jnp.asarray(pts), index.config, mesh=mesh
            )
            index._adopt_base(base, ids)
        _recover_wal(index, path)
        return index, manifest.get("extra_meta", {})

    index = ShardedMutableHilbertIndex(
        config=config, mesh=mesh,
        buffer_capacity=int(manifest["buffer_capacity"]),
        max_segments=int(manifest["max_segments"]),
    )
    _restore_lsm(index, manifest, state)
    index._gen = int(manifest["gen"])
    if manifest.get("dim") is not None:
        index._dim = int(manifest["dim"])
        index._alloc_buffers()
        bsh = np.asarray(state["buffer_shard"], np.int64)
        for i in range(bsh.shape[0]):
            s = int(bsh[i])
            c = int(index._buf_count[s])
            index._buf_pts[s, c] = state["buffer_points"][i]
            index._buf_ids[s, c] = state["buffer_ids"][i]
            index._buf_count[s] = c + 1
    if manifest.get("has_bounds") and "bounds" in state:
        index._bounds = np.asarray(state["bounds"], np.uint32)
        index._route_lo = np.asarray(state["route_lo"], np.float32)
        index._route_hi = np.asarray(state["route_hi"], np.float32)
    repl = NamedSharding(mesh, P())
    for entry in manifest["segments"]:
        loaded = _load_segment_bundles(path, entry, saved)
        shard_indexes = [idx for idx, _ in loaded]
        id_maps = np.stack([ids for _, ids in loaded])
        stack, points = stack_shard_indexes(
            mesh, shard_indexes, id_maps,
            store_points=all(ix.points is not None for ix in shard_indexes),
        )
        index.segments.append(ShardedSegment(
            stack=stack, points=points,
            quant=jax.device_put(shard_indexes[0].quant, repl),
            gen=int(entry["gen"]),
            n_valid=np.asarray(entry["n_valid"], np.int64),
            pad_max=int(entry["pad_max"]),
            ids_host=id_maps,
        ))
        if index._perms is None:
            index._perms = jax.device_put(
                shard_indexes[0].forest.perms, repl
            )
            index._flips = jax.device_put(
                shard_indexes[0].forest.flips, repl
            )
    _recover_wal(index, path)
    return index, manifest.get("extra_meta", {})


def _restore_lsm(index, manifest: Dict,
                 state: Dict[str, np.ndarray]) -> None:
    index._lsm.next_id = int(manifest["next_id"])
    index._lsm.alive = np.asarray(state["alive"], np.bool_)
    index._lsm.track_values = manifest.get("track_values")
    if "values" in state:
        index._lsm.values = state["values"]


def _load_segment_bundles(path: str, entry: Dict, n_shards: int):
    """One v4 generation's per-shard (HilbertIndex, ext-id array) pairs."""
    out = []
    for s in range(n_shards):
        idx, extras, _ = load_index_bundle(
            os.path.join(path, "segments", entry["name"], f"shard_{s:05d}"),
            kind=_SEG_SHARD_KIND,
        )
        out.append((idx, np.asarray(jax.device_get(extras["ids"]),
                                    np.int32)))
    return out


def _gather_live_v4(path: str, manifest: Dict, state: Dict
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Live (ids, points) of a v4 checkpoint, sorted by external id."""
    saved = int(manifest["n_shards"])
    parts_i = [np.asarray(state["buffer_ids"], np.int32)]
    parts_p = [np.asarray(state["buffer_points"], np.float32)]
    for entry in manifest["segments"]:
        for s, (idx, ids) in enumerate(
            _load_segment_bundles(path, entry, saved)
        ):
            if idx.points is None:
                raise ValueError(
                    "cannot reshard a sharded-mutable checkpoint whose "
                    "segments lack stored points (IndexConfig("
                    "store_points=False)); load on a matching mesh instead"
                )
            nv = int(entry["n_valid"][s])
            parts_i.append(ids[:nv])
            parts_p.append(np.asarray(jax.device_get(idx.points))[:nv])
    ids = np.concatenate(parts_i)
    pts = np.concatenate(parts_p)
    live = np.asarray(state["alive"], np.bool_)[ids]
    ids, pts = ids[live], pts[live]
    order = np.argsort(ids, kind="stable")
    return ids[order].astype(np.int32), np.ascontiguousarray(pts[order])


def load_sharded_mutable_as_mutable(path: str, *, kind: str = _DEFAULT_KIND):
    """Degrade a v4 checkpoint onto ONE device: the mutable single-device
    layout, external ids (and values) preserved.

    The reshard-to-one story for serving workers without a mesh: live rows
    gather in external-id order (buffered rows included) and seal as one
    :class:`repro.index.MutableHilbertIndex` segment — a compact-on-load,
    like the multi-device reshard.  Returns that mutable index.
    """
    from repro.index.facade import HilbertIndex
    from repro.index.mutable import MutableHilbertIndex, Segment

    mpath = os.path.join(path, _MANIFEST)
    with open(mpath) as f:
        manifest = json.load(f)
    if manifest.get("kind") != kind:
        raise ValueError(
            f"{path!r} is not a sharded-mutable checkpoint of kind {kind!r} "
            f"(kind={manifest.get('kind')!r})"
        )
    state = _restore_state_bundle(
        os.path.join(path, "state"), manifest.get("state_step")
    )
    ids, pts = _gather_live_v4(path, manifest, state)
    config = dataclasses.replace(
        IndexConfig.from_dict(manifest["config"]), shards=None
    )
    mut = MutableHilbertIndex(
        config, buffer_capacity=int(manifest["buffer_capacity"]),
        max_segments=int(manifest["max_segments"]),
    )
    _restore_lsm(mut, manifest, state)
    if manifest.get("dim") is not None:
        d = int(manifest["dim"])
        mut._dim = d
        mut._buf_points = np.zeros((mut.buffer_capacity, d), np.float32)
        mut._buf_ids = np.full((mut.buffer_capacity,), -1, np.int32)
    if ids.size:
        mut.segments = [Segment(
            index=HilbertIndex.build(jnp.asarray(pts), config),
            ids=ids, gen=0,
        )]
        mut._gen = 1
    # Acknowledged writes survive the degrade-to-one-device path too: the
    # sharded WAL's records are layout-agnostic ops, so they replay into
    # (and re-attach to) the single-device facade directly.
    _recover_wal(mut, path)
    return mut
