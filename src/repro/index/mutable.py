"""MutableHilbertIndex: LSM-style streaming mutation on top of HilbertIndex.

The paper's headline Task-2 result — Hilbert sort makes forest construction
the *fastest* entry — is exactly the property that makes merge-based dynamic
maintenance cheap: re-sorting a few hundred thousand points is milliseconds,
so segments can be rebuilt wholesale instead of patched in place.  This
module layers classic LSM machinery over the immutable facade:

* **Write buffer** — a fixed-capacity in-RAM array of freshly inserted
  points, searched exactly (:func:`repro.core.search.brute_force_topk`).
  Fixed capacity keeps the jitted brute-force stage's shapes stable.
* **Sealed segments** — when the buffer fills (or :meth:`flush` is called)
  its live rows become an ordinary immutable :class:`HilbertIndex` built via
  the existing fast path, plus an id-remap array giving each local row its
  stable external id.
* **Tombstones** — deletes only flip a bit in a dense ``alive`` mask; search
  masks dead candidates during the cross-segment merge, and each segment's
  per-query ``k`` is inflated by its dead count (rounded up to a power of
  two) so tombstones cannot eat result slots.
* **Tiered compaction** — when segments pile up, the smallest two are merged
  by concatenating their stored points, dropping tombstoned rows for good,
  re-sorting (one cheap Hilbert-forest build), and remapping ids.
  :meth:`compact` merges everything into one segment, after which search is
  equivalent to a from-scratch :class:`HilbertIndex.build` over the
  surviving points (segments keep rows in external-id order, i.e. insertion
  order, so the rebuild sees the same point sequence).

Search fans out over buffer + segments and merges per-source top-k into one
exact top-k (the same associative merge argument as ``core/knn_graph.py``:
the global top-k of a union is the top-k of per-source top-k's).  External
ids are stable for the life of the index — they survive flushes and
compactions — and rows never move between sources except through them.

Persistence is a multi-bundle checkpoint: one ``repro.checkpoint`` bundle
per segment, one for the buffer/tombstone/value state, committed by an
atomically renamed top-level manifest (see
:func:`repro.checkpoint.atomic_write_json`).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import shutil
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint
from repro.checkpoint import wal as wal_lib
from repro.core import search as search_lib
from repro.core.types import SearchParams
from repro.index.config import IndexConfig
from repro.obs.dispatch import dispatch_scope
from repro.obs.trace import span
from repro.testing.faults import fault_point
from repro.index.facade import (
    HilbertIndex,
    load_index_bundle,
    save_index_bundle,
)

__all__ = [
    "LsmIdSpace",
    "MutableHilbertIndex",
    "Segment",
    "WalFacade",
    "dense_values_at",
    "load_mutable_bundle",
    "replay_wal_records",
    "save_mutable_bundle",
]


def dense_values_at(values: np.ndarray, ids, fill=0) -> jax.Array:
    """Gather rows of a dense by-id ``values`` array for search-result ids.

    The one -1-slot masking gather both serving layouts share: ``ids`` may
    contain ``-1`` padding (fewer than k hits), which surfaces as ``fill``;
    other ids are clipped into range.  Broadcasting handles values of any
    trailing shape (scalar tokens or vector payloads).
    """
    idn = np.asarray(jax.device_get(ids))
    safe = np.clip(idn, 0, values.shape[0] - 1)
    out = values[safe]
    mask = (idn >= 0).reshape(idn.shape + (1,) * (out.ndim - idn.ndim))
    return jnp.asarray(np.where(mask, out, fill))

_MANIFEST = "mutable_manifest.json"
_SEGMENT_KIND = "mutable_segment"
_DEFAULT_KIND = "mutable_hilbert_index"
_MAX_IDS = 2**31 - 1  # external ids are int32


def _pow2_ceil(x: int) -> int:
    """0 for x<=0, else the smallest power of two >= x."""
    return 0 if x <= 0 else 1 << (int(x) - 1).bit_length()


class LsmIdSpace:
    """External-id allocation, tombstones, and per-point values — the LSM
    bookkeeping shared by every mutable facade.

    Extracted from :class:`MutableHilbertIndex` so the sharded streaming
    index (:class:`repro.index.ShardedMutableHilbertIndex`) reuses identical
    semantics: ids are dense int32 assigned at insert and stable for the
    life of the index, ``alive`` is a dense by-id tombstone mask, and
    ``values`` (optional) is a dense by-id payload array whose tracking mode
    is pinned by the first insert.  ``delete_epoch`` bumps on every
    effective delete so owners can cache per-segment dead counts.
    """

    def __init__(self):
        self.next_id = 0
        self.alive = np.zeros((0,), np.bool_)  # dense by external id
        self.values: Optional[np.ndarray] = None  # dense by external id
        self.track_values: Optional[bool] = None
        self.delete_epoch = 0  # bumps on delete; invalidates dead caches

    @property
    def n_live(self) -> int:
        return int(np.count_nonzero(self.alive))

    @property
    def n_deleted(self) -> int:
        return int(self.next_id - self.n_live)

    def prepare(
        self, points, values, dim: Optional[int]
    ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Normalize + fully validate an insert WITHOUT mutating anything.

        The shared preamble of both mutable facades' ``insert``: device_get
        and promote points to (m, d) fp32, run :meth:`validate`, and check
        against the owner's pinned ``dim`` (``None`` = not pinned yet).
        Returns host ``(points, values)``; a raise here leaves the index
        unchanged.  Callers then pin dim / allocate buffers and call
        :meth:`register`.
        """
        pts = np.asarray(jax.device_get(points), np.float32)
        if pts.ndim == 1:
            pts = pts[None, :]
        if pts.ndim != 2:
            raise ValueError(f"points must be (m, d), got shape {pts.shape}")
        if pts.shape[0] == 0:
            return pts, None
        vals = self.validate(pts.shape[0], values)
        if dim is not None and pts.shape[1] != dim:
            raise ValueError(
                f"dim mismatch: index is {dim}, got {pts.shape[1]}"
            )
        return pts, vals

    def validate(self, m: int, values) -> Optional[np.ndarray]:
        """Pre-mutation checks for an m-row insert; returns host values.

        Raises without touching any state (a failed insert must leave the
        index unchanged — including NOT pinning the values mode).
        """
        if self.track_values is not None and (
            (values is not None) != self.track_values
        ):
            raise ValueError(
                "inconsistent values tracking: every insert must carry values "
                "or none may (first insert decides)"
            )
        vals = None
        if values is not None:
            vals = np.asarray(jax.device_get(values))
            if vals.shape[:1] != (m,):
                raise ValueError(f"values must be (m, ...) with m={m}")
        if self.next_id + m > _MAX_IDS:
            raise OverflowError("external id space (int32) exhausted")
        return vals

    def register(self, m: int, vals: Optional[np.ndarray]) -> np.ndarray:
        """Allocate m external ids; extend alive/values. Call validate first."""
        if self.track_values is None:
            self.track_values = vals is not None
        ids = np.arange(self.next_id, self.next_id + m, dtype=np.int32)
        self.next_id += m
        self.alive = np.concatenate([self.alive, np.ones((m,), np.bool_)])
        if vals is not None:
            self.values = (
                vals.copy()
                if self.values is None
                else np.concatenate([self.values, vals])
            )
        return ids

    def delete(self, ids) -> int:
        """Tombstone ids; returns the newly-dead count. KeyError on unknown."""
        idn = np.atleast_1d(np.asarray(jax.device_get(ids))).astype(np.int64)
        if idn.size == 0:
            return 0
        if (idn < 0).any() or (idn >= self.next_id).any():
            bad = idn[(idn < 0) | (idn >= self.next_id)]
            raise KeyError(f"unknown external ids: {bad[:8].tolist()}")
        uniq = np.unique(idn)
        newly = int(np.count_nonzero(self.alive[uniq]))
        self.alive[uniq] = False
        if newly:
            self.delete_epoch += 1
        return newly

    def values_at(self, ids, fill=0) -> jax.Array:
        if self.values is None:
            raise ValueError("this index tracks no values (insert them)")
        return dense_values_at(self.values, ids, fill=fill)

    def values_dense(self) -> jax.Array:
        if self.values is None:
            raise ValueError("this index tracks no values (insert them)")
        return jnp.asarray(self.values)

    def clone(self) -> "LsmIdSpace":
        """Deep copy of the host bookkeeping (the snapshot/swap hook).

        The arrays are small relative to sealed segments (1 byte/id + the
        values payload), so cloning is cheap enough to run under a serving
        engine's write lock.
        """
        c = LsmIdSpace()
        c.next_id = self.next_id
        c.alive = self.alive.copy()
        c.values = None if self.values is None else self.values.copy()
        c.track_values = self.track_values
        c.delete_epoch = self.delete_epoch
        return c


@dataclasses.dataclass(eq=False)  # identity equality: segments hold arrays
class Segment:
    """One sealed immutable segment: an index plus its external-id remap.

    ``ids[row] = external id`` of the row-th point handed to the segment's
    build (ascending, because flush/compaction keep insertion order), so a
    local search result maps to stable ids with one gather.
    """

    index: HilbertIndex
    ids: np.ndarray  # (n,) int32, ascending external ids
    gen: int  # monotone generation tag (stable on-disk segment name)
    # With IndexConfig.seal_pow2, seal builds cyclically repeat real rows
    # up to a power-of-two count for shape-stable jitted search; rows past
    # ``n_valid`` are duplicates of earlier ones (same external id, so the
    # cross-source merge dedups them).  -1 = unpadded (n_valid == n_points).
    n_valid: int = -1
    # dead-count cache: recomputed only when the owner's delete epoch moves.
    dead_cache: int = dataclasses.field(default=-1, repr=False)
    dead_epoch: int = dataclasses.field(default=-1, repr=False)

    @property
    def n_points(self) -> int:
        return int(self.ids.shape[0])

    @property
    def n_real(self) -> int:
        """Rows that are NOT pow2 padding duplicates (a prefix of ids)."""
        return self.n_valid if self.n_valid >= 0 else self.n_points

    @property
    def n_pad(self) -> int:
        return self.n_points - self.n_real

    def memory_bytes(self) -> int:
        return self.index.memory_report()["resident_bytes"] + self.ids.nbytes

    def content_uid(self) -> str:
        """Content address for on-disk dedup: hashes ids + quantized codes.

        Two segments with equal uids hold the same points under the same
        external ids, so a save may safely skip rewriting a bundle that
        already carries this uid — even if it was written by a different
        index instance reusing the same checkpoint path.  Codes are hashed
        in their resident nibble-packed layout, so bundles written by the
        old unpacked-uint8 format never collide with packed ones and are
        rewritten on the first save after an upgrade.
        """
        h = hashlib.sha1()
        h.update(np.int64(self.gen).tobytes())
        h.update(np.asarray(self.ids.shape + self.index.codes_master.shape,
                            np.int64).tobytes())
        h.update(self.ids.tobytes())
        h.update(np.asarray(self.index.codes_master).tobytes())
        return h.hexdigest()


class WalFacade:
    """WAL attachment + log-then-apply hooks shared by both mutable facades.

    Host classes provide ``self._lsm`` (an :class:`LsmIdSpace`),
    ``self._dim``, and initialise ``self._wal = None``.  Mutating methods
    call :meth:`_wal_log_insert` / :meth:`_wal_log_delete` BEFORE touching
    any state: the record is durable (or the append raised) by the time the
    op applies, so an acknowledged mutation can never be lost to a crash.
    """

    _wal: Optional[wal_lib.WriteAheadLog]

    @property
    def wal(self) -> Optional[wal_lib.WriteAheadLog]:
        return self._wal

    def enable_wal(
        self, path: str, config: Optional[wal_lib.WalConfig] = None
    ) -> wal_lib.WriteAheadLog:
        """Attach a write-ahead log at ``<path>/wal.log``.

        ``path`` is the checkpoint directory this index saves to:
        ``save(path)`` truncates the log at its commit point, and
        ``load(path)`` replays + re-attaches it automatically.  The file
        must be fresh (no unreplayed records) — recovering an existing
        log is ``load()``'s job, not this method's.
        """
        if self._wal is not None:
            raise ValueError("a WAL is already attached to this index")
        os.makedirs(path, exist_ok=True)
        self._wal = wal_lib.WriteAheadLog(wal_lib.wal_path(path), config)
        return self._wal

    def detach_wal(self) -> Optional[wal_lib.WriteAheadLog]:
        """Detach (without closing) and return the WAL, if any."""
        w, self._wal = self._wal, None
        return w

    def _wal_log_insert(self, op: str, points, values) -> None:
        if self._wal is None:
            return
        # prepare() validates without mutating, so nothing is logged for
        # an insert that would raise — and a WAL failure below leaves the
        # index untouched (the op is then applied by nobody).
        pts, vals = self._lsm.prepare(points, values, self._dim)
        if pts.shape[0] == 0:
            return
        arrays = {"points": pts}
        if vals is not None:
            arrays["values"] = vals
        self._wal.append(op, arrays, {"next_id": int(self._lsm.next_id)})

    def _wal_log_delete(self, ids) -> None:
        if self._wal is None:
            return
        idn = np.atleast_1d(np.asarray(jax.device_get(ids))).astype(np.int64)
        if idn.size == 0:
            return
        if (idn < 0).any() or (idn >= self._lsm.next_id).any():
            bad = idn[(idn < 0) | (idn >= self._lsm.next_id)]
            raise KeyError(f"unknown external ids: {bad[:8].tolist()}")
        self._wal.append(
            "delete", {"ids": idn.astype(np.int32)},
            {"next_id": int(self._lsm.next_id)},
        )


class MutableHilbertIndex(WalFacade):
    """Streaming insert/delete/search over an LSM of Hilbert-forest segments.

    Typical lifecycle::

        mut = MutableHilbertIndex(IndexConfig(), buffer_capacity=4096)
        ids = mut.insert(points)          # stable external ids
        mut.delete(ids[:10])              # tombstoned, invisible to search
        hits, d2 = mut.search(queries, SearchParams(k=30))
        mut.compact()                     # one segment, tombstones dropped
        mut.save(path); mut = MutableHilbertIndex.load(path)

    ``insert`` may carry per-point ``values`` (e.g. kNN-LM next tokens);
    retrieve them for search hits with :meth:`values_at`.
    """

    def __init__(
        self,
        config: Optional[IndexConfig] = None,
        *,
        buffer_capacity: int = 4096,
        max_segments: int = 8,
    ):
        if buffer_capacity < 1:
            raise ValueError(f"buffer_capacity must be >= 1, got {buffer_capacity}")
        if max_segments < 1:
            raise ValueError(f"max_segments must be >= 1, got {max_segments}")
        self.config = IndexConfig() if config is None else config
        self.buffer_capacity = int(buffer_capacity)
        self.max_segments = int(max_segments)
        self.segments: List[Segment] = []
        self._dim: Optional[int] = None
        self._buf_points: Optional[np.ndarray] = None  # (capacity, d) f32
        self._buf_ids: Optional[np.ndarray] = None  # (capacity,) int32
        self._buf_count = 0
        self._lsm = LsmIdSpace()  # external ids / tombstones / values
        self._gen = 0
        self._wal: Optional[wal_lib.WriteAheadLog] = None

    # -- LsmIdSpace shims (the historical attribute names, kept so segment
    # bookkeeping below and external pokes keep reading naturally) ----------

    @property
    def _alive(self) -> np.ndarray:
        return self._lsm.alive

    @_alive.setter
    def _alive(self, v) -> None:
        self._lsm.alive = v

    @property
    def _next_id(self) -> int:
        return self._lsm.next_id

    @_next_id.setter
    def _next_id(self, v) -> None:
        self._lsm.next_id = v

    @property
    def _values(self) -> Optional[np.ndarray]:
        return self._lsm.values

    @_values.setter
    def _values(self, v) -> None:
        self._lsm.values = v

    @property
    def _track_values(self) -> Optional[bool]:
        return self._lsm.track_values

    @_track_values.setter
    def _track_values(self, v) -> None:
        self._lsm.track_values = v

    @property
    def _delete_epoch(self) -> int:
        return self._lsm.delete_epoch

    # -- introspection -------------------------------------------------------

    @property
    def dim(self) -> Optional[int]:
        return self._dim

    @property
    def n_segments(self) -> int:
        return len(self.segments)

    @property
    def n_live(self) -> int:
        """Points visible to search (inserted, not deleted)."""
        return int(np.count_nonzero(self._alive))

    @property
    def n_deleted(self) -> int:
        return int(self._next_id - self.n_live)

    @property
    def n_buffered(self) -> int:
        """Live points still in the write buffer (not yet in a segment)."""
        if self._buf_count == 0:
            return 0
        return int(np.count_nonzero(self._alive[self._buf_ids[: self._buf_count]]))

    def memory_report(self) -> Dict[str, Any]:
        """Bytes for ALL resident state: segments, buffer, values, tombstones."""
        per_segment = [seg.memory_bytes() for seg in self.segments]
        buffer_bytes = 0
        if self._buf_points is not None:
            buffer_bytes = self._buf_points.nbytes + self._buf_ids.nbytes
        rep: Dict[str, Any] = {
            "segments_bytes": int(sum(per_segment)),
            "buffer_bytes": int(buffer_bytes),
            "values_bytes": 0 if self._values is None else int(self._values.nbytes),
            "tombstone_bytes": int(self._alive.nbytes),
            "per_segment": [int(b) for b in per_segment],
            "n_segments": self.n_segments,
            "n_live": self.n_live,
            "n_deleted": self.n_deleted,
            "n_buffered": self.n_buffered,
        }
        rep["total_bytes"] = (
            rep["segments_bytes"]
            + rep["buffer_bytes"]
            + rep["values_bytes"]
            + rep["tombstone_bytes"]
        )
        return rep

    def __repr__(self) -> str:
        mb = self.memory_report()["total_bytes"] / 1e6
        return (
            f"MutableHilbertIndex(n_live={self.n_live}, "
            f"n_segments={self.n_segments}, "
            f"buffered={self.n_buffered}/{self.buffer_capacity}, "
            f"deleted={self.n_deleted}, dim={self._dim}, {mb:.2f} MB)"
        )

    # -- mutation ------------------------------------------------------------

    def _register(
        self, points, values
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Shared insert bookkeeping: dims, values mode, ids, alive mask.

        ``prepare`` validates EVERYTHING before any state mutation
        (including pinning the values mode): a failed insert must leave
        the index unchanged.
        """
        pts, vals = self._lsm.prepare(points, values, self._dim)
        if pts.shape[0] == 0:
            return pts, np.zeros((0,), np.int32)
        if self._dim is None:
            self._dim = int(pts.shape[1])
            self._buf_points = np.zeros(
                (self.buffer_capacity, self._dim), np.float32
            )
            self._buf_ids = np.full((self.buffer_capacity,), -1, np.int32)
        return pts, self._lsm.register(pts.shape[0], vals)

    def insert(
        self, points: jax.Array, values: Optional[jax.Array] = None
    ) -> np.ndarray:
        """Insert points; each sealed segment later rides the paper's fast
        Hilbert-sort build (Algorithm 1 preprocessing) — what makes
        merge-based maintenance cheap.

        Args:
          points: (m, d) fp32 rows (a single (d,) row is promoted).
          values: optional (m, ...) per-point payloads; the first insert
            pins whether the index tracks values.

        Returns:
          (m,) int32 stable external ids.

        Points land in the write buffer (searchable immediately, exactly);
        each buffer fill seals a segment, and tier merging keeps the segment
        count at most ``max_segments``.  ``values`` attaches one payload per
        point — either every insert carries values or none does.

        With a WAL attached the insert is logged BEFORE any state changes
        (log-then-apply): a crash at any later instant replays it, and a
        failed log (:class:`repro.checkpoint.WalWriteError`) leaves the
        index untouched — the insert was never acknowledged.
        """
        self._wal_log_insert("insert", points, values)
        pts, ids = self._register(points, values)
        m = pts.shape[0]
        if m == 0:
            return ids

        done = 0
        while done < m:
            take = min(self.buffer_capacity - self._buf_count, m - done)
            sl = slice(self._buf_count, self._buf_count + take)
            self._buf_points[sl] = pts[done : done + take]
            self._buf_ids[sl] = ids[done : done + take]
            self._buf_count += take
            done += take
            if self._buf_count >= self.buffer_capacity:
                self.flush()
        self._maybe_merge_tiers()
        return ids

    def bulk_load(
        self, points: jax.Array, values: Optional[jax.Array] = None
    ) -> np.ndarray:
        """Seal a whole corpus as ONE segment, bypassing the write buffer.

        The LSM bulk-load path: the initial corpus of a store should be one
        large segment (search latency/recall identical to a static
        ``HilbertIndex``), not ``n/buffer_capacity`` small ones.  Returns
        external ids like :meth:`insert`.
        """
        self._wal_log_insert("bulk_load", points, values)
        if self._buf_count:
            self.flush()
        pts, ids = self._register(points, values)
        if pts.shape[0] == 0:
            raise ValueError("bulk_load needs a non-empty (m, d) corpus")
        self.segments.append(self._build_segment(pts, ids))
        self._maybe_merge_tiers()
        return ids

    def delete(self, ids) -> int:
        """Tombstone external ids; returns how many were newly deleted.

        Out-of-range ids raise ``KeyError``; already-deleted ids are a no-op
        (idempotent).  Rows are physically dropped at the next flush (buffer
        rows) or compaction touching their segment.
        """
        self._wal_log_delete(ids)
        return self._lsm.delete(ids)

    # -- write-ahead log: wal / enable_wal / detach_wal and the log-then-
    # apply hooks come from WalFacade (shared with the sharded facade) ------

    def _segment_dead(self, seg: Segment) -> int:
        """Tombstone count among a segment's REAL rows, cached between
        deletes (pow2 padding duplicates are accounted separately).

        Safe under the engine's SHARED read lock: deletes (the only thing
        that moves ``_delete_epoch``) hold the write side, so concurrent
        readers can at worst race an identical idempotent fill — and the
        cache value is written BEFORE the epoch stamp, so a reader that
        observes the fresh epoch always reads the fresh count.
        """
        if seg.dead_epoch != self._delete_epoch:
            seg.dead_cache = seg.n_real - int(
                np.count_nonzero(self._alive[seg.ids[: seg.n_real]])
            )
            seg.dead_epoch = self._delete_epoch
        return seg.dead_cache

    def rewrite_pressure(self, params: Optional[SearchParams] = None) -> int:
        """Segments so tombstoned that dead rows can crowd live neighbors
        out of the stage-2 candidate pool under ``params``.

        This is the condition that used to trigger a rewrite INSIDE
        ``search()``.  The serving engine searches with
        ``allow_rewrite=False`` (its read path must not mutate under the
        shared read lock), so the same condition is surfaced here as a
        maintenance trigger instead: a nonzero pressure trips
        :class:`~repro.serve.engine.MaintenancePolicy` and the maintainer
        compacts off the query path.
        """
        if params is None:
            params = SearchParams()
        cap = params.k2 * (2 * params.h + 1)
        n = 0
        for seg in list(self.segments):
            dead = self._segment_dead(seg)
            need = (params.k + dead) * (2 if seg.n_pad else 1)
            if dead > 0 and need > cap and seg.index.points is not None:
                n += 1
        return n

    # -- segment lifecycle ---------------------------------------------------

    def _build_segment(self, pts: np.ndarray, ids: np.ndarray,
                       *, pad: bool = False) -> Segment:
        # config.store_points is honored: True (the default) keeps raw fp32
        # points on each segment so compaction can re-sort them; False saves
        # that RAM for serving-only deployments at the cost of compaction
        # (tier merges skip point-less segments; compact() raises).
        n_valid = int(pts.shape[0])
        if pad and self.config.seal_pow2:
            # Shape-stable seals: cyclically repeat real rows up to the
            # next power of two.  Duplicates share their original's
            # external id, so the merge dedups them; compact() and bulk
            # loads never pad (pad=False) and stay bit-equal to a fresh
            # build over the live rows.
            target = _pow2_ceil(max(n_valid, 1))
            if target > n_valid:
                reps = -(-target // n_valid)
                pts = np.tile(pts, (reps, 1))[:target]
                ids = np.tile(ids, reps)[:target]
        with span("lsm.segment_build", rows=int(pts.shape[0])), \
                dispatch_scope("lsm.segment_build"):
            index = HilbertIndex.build(jnp.asarray(pts), self.config)
        seg = Segment(index=index, ids=np.ascontiguousarray(ids, np.int32),
                      gen=self._gen, n_valid=n_valid)
        self._gen += 1
        return seg

    def flush(self) -> Optional[Segment]:
        """Seal the write buffer's live rows into an immutable segment.

        Dead buffer rows are dropped here for good.  No-op (returns None) on
        an empty or fully tombstoned buffer.
        """
        if self._buf_count == 0:
            return None
        ids = self._buf_ids[: self._buf_count]
        live = self._alive[ids]
        pts = self._buf_points[: self._buf_count][live].copy()
        ids = ids[live].copy()
        self._buf_count = 0
        if ids.size == 0:
            return None
        seg = self._build_segment(pts, ids, pad=True)
        self.segments.append(seg)
        return seg

    def _merge_segments(self, to_merge: Sequence[Segment],
                        *, pad: bool = False) -> Optional[Segment]:
        """Replace ``to_merge`` with one segment; tombstoned rows vanish."""
        for seg in to_merge:
            if seg.index.points is None:
                raise ValueError(
                    "cannot compact a segment built without stored points "
                    "(IndexConfig(store_points=False), or a store_points="
                    "False index adopted via from_index)"
                )
        # Pow2 padding rows (duplicates past n_real) are excluded here, so
        # merges — and in particular compact() — see exactly the real rows.
        pts = np.concatenate(
            [np.asarray(seg.index.points, np.float32)[: seg.n_real]
             for seg in to_merge]
        )
        ids = np.concatenate([seg.ids[: seg.n_real] for seg in to_merge])
        live = self._alive[ids]
        pts, ids = pts[live], ids[live]
        # External-id order == insertion order: a full compaction therefore
        # feeds the rebuild the same point sequence a fresh build would see.
        order = np.argsort(ids, kind="stable")
        pts, ids = pts[order], ids[order]
        self.segments = [s for s in self.segments if s not in to_merge]
        if ids.size == 0:
            return None
        seg = self._build_segment(pts, ids, pad=pad)
        self.segments.append(seg)
        return seg

    def _maybe_merge_tiers(self) -> None:
        while len(self.segments) > self.max_segments:
            # Only segments holding raw points can be re-sorted; without
            # store_points the segment count is unbounded by design.
            mergeable = [s for s in self.segments if s.index.points is not None]
            if len(mergeable) < 2:
                return
            smallest = sorted(mergeable, key=lambda s: s.n_points)[:2]
            self._merge_segments(smallest, pad=True)

    def compact(self) -> "MutableHilbertIndex":
        """Full compaction: flush, then merge ALL segments into one.

        Afterwards the index holds at most one segment containing exactly
        the live points in insertion order, and every tombstoned row has
        been physically dropped.  Returns self (chainable).
        """
        with span("lsm.compact", segments=len(self.segments)):
            self.flush()
            if self.segments:
                self._merge_segments(list(self.segments))
        return self

    # -- serving-engine hooks ------------------------------------------------

    def snapshot(self) -> "MutableHilbertIndex":
        """Cheap shared-buffer copy for off-path maintenance (double-buffer).

        Sealed segments are immutable, so the snapshot SHARES their arrays
        (zero copy — the dominant state) under fresh :class:`Segment`
        wrappers (per-segment dead-count caches must not race between the
        serving copy and the shadow); only the write buffer and the LSM
        bookkeeping (alive mask, values, id cursor) are deep-copied.  The
        snapshot is a fully independent index: a serving engine hands it to
        a maintenance thread, compacts it off the query path, replays the
        writes that arrived meanwhile, and swaps it in (see
        :mod:`repro.serve.engine`).
        """
        snap = MutableHilbertIndex(
            config=self.config,
            buffer_capacity=self.buffer_capacity,
            max_segments=self.max_segments,
        )
        snap._dim = self._dim
        if self._dim is not None:
            snap._buf_points = self._buf_points.copy()
            snap._buf_ids = self._buf_ids.copy()
        snap._buf_count = self._buf_count
        snap._lsm = self._lsm.clone()
        snap._gen = self._gen
        snap.segments = [
            Segment(index=seg.index, ids=seg.ids, gen=seg.gen,
                    n_valid=seg.n_valid)
            for seg in self.segments
        ]
        # Deliberately NOT copied: the WAL.  A snapshot is the engine's
        # shadow — replaying writes onto it must not re-log them; the live
        # index's WAL transfers at swap time (see serve/engine.py).
        return snap

    def maintenance_stats(self) -> Dict[str, Any]:
        """The trigger signals a background maintainer watches (host-only).

        ``tombstone_ratio`` is dead/allocated ids; ``mergeable_segments``
        counts segments that actually hold raw points (the only ones a
        merge or compaction can re-sort).
        """
        next_id = max(self._next_id, 1)
        return {
            "n_segments": self.n_segments,
            "mergeable_segments": sum(
                1 for s in self.segments if s.index.points is not None
            ),
            "n_live": self.n_live,
            "n_deleted": self.n_deleted,
            "n_buffered": self.n_buffered,
            "tombstone_ratio": float(self.n_deleted) / float(next_id),
        }

    # -- search --------------------------------------------------------------

    def search(
        self,
        queries: jax.Array,
        params: Optional[SearchParams] = None,
        *,
        backend: str = "auto",
        query_chunk: Optional[int] = None,
        allow_rewrite: bool = True,
    ) -> Tuple[jax.Array, jax.Array]:
        """Fan-out Algorithm-1 top-k over buffer + segments, merged exactly.

        Args:
          queries: (Q, d) fp32 query batch.
          params: Algorithm-1 hyper-parameters (paper Table 1 names);
            applied per segment, with per-segment ``k`` inflation for
            tombstones (:func:`repro.core.search.inflate_k`).
          backend: kernel routing for the segment searches.
          query_chunk: per-dispatch chunk cap (default
            ``config.query_chunk``).
          allow_rewrite: permit read-triggered compaction (below).  The
            serving engine passes ``False``: its searches run under a
            SHARED read lock, so the read path must not mutate segments —
            the same condition is surfaced via :meth:`rewrite_pressure`
            and handled by the maintainer off the query path instead.

        Returns (ids (Q, k), sq-distances (Q, k)) like ``HilbertIndex.search``
        but with **external** ids; when fewer than k live points exist the
        tail is padded with id -1 / distance +inf.  Segment distances are
        ADC (asymmetric vs 4-bit codes) as in the paper; buffer distances
        are exact fp32 — both approximate the true metric, and the merge
        compares them directly.  Each segment is queried for
        ``k + (its tombstone count, rounded up to a power of two)`` so
        masked rows cannot displace live results — up to the stage-2
        candidate pool (``k2*(2h+1)``).  A
        segment tombstoned past that bound is rewritten on the spot
        (read-triggered compaction) when it stores raw points; without
        stored points (or with ``allow_rewrite=False``) its recall
        degrades until it is compacted or the ids are reinserted.
        """
        if params is None:
            params = SearchParams()
        q = jnp.asarray(queries)
        qn, k = q.shape[0], params.k
        # The buffer search and the merge run on qp rows, a power of two,
        # so batch sizes compile log-many times (segments bucket their own).
        qp = _pow2_ceil(qn)
        # stage-2 candidate pool per segment; lax.top_k caps k there.
        cap = params.k2 * (2 * params.h + 1)
        parts_ids: List[np.ndarray] = []
        parts_d: List[np.ndarray] = []
        for seg in list(self.segments):
            dead = self._segment_dead(seg)
            # Pow2 padding duplicates each real row at most twice (pad <
            # n_real by construction), so a padded segment needs 2x the
            # candidate slots to guarantee the same count of DISTINCT live
            # results; unpadded segments keep the historical k + dead.
            need = (k + dead) * (2 if seg.n_pad else 1)
            if (allow_rewrite and dead > 0 and need > cap
                    and seg.index.points is not None):
                # So many tombstones that dead candidates could crowd live
                # neighbors out of the stage-1/2 candidate pools (k can no
                # longer be inflated past the pool size).  Read-triggered
                # compaction: rewrite just this segment, dropping its dead
                # rows for good, then search the clean replacement.
                seg = self._merge_segments([seg], pad=True)
                if seg is None:  # segment was fully tombstoned
                    continue
                dead = 0
            # The segment is asked for k plus a pow2 bucket of its dead
            # count (as on the sharded layout): k is a static argument of
            # the compiled search, so deletes then recompile it log-many
            # times instead of once per distinct tombstone count.
            need = (k + _pow2_ceil(dead)) * (2 if seg.n_pad else 1)
            k_seg = search_lib.inflate_k(k, need - k, cap)
            sids, sd2 = seg.index.search(
                q, dataclasses.replace(params, k=k_seg),
                backend=backend, query_chunk=query_chunk,
            )
            sids = np.clip(np.asarray(sids), 0, seg.n_points - 1)
            parts_ids.append(seg.ids[sids])
            parts_d.append(np.asarray(sd2, np.float32))
        if self.n_buffered:
            valid = np.zeros((self.buffer_capacity,), np.bool_)
            bids = self._buf_ids[: self._buf_count]
            valid[: self._buf_count] = self._alive[bids]
            with dispatch_scope("lsm.buffer_search"):
                idx, bd2 = search_lib.brute_force_topk(
                    jnp.pad(q, ((0, qp - qn), (0, 0))),
                    jnp.asarray(self._buf_points), jnp.asarray(valid),
                    k=min(k, self.buffer_capacity),
                )
            parts_ids.append(self._buf_ids[np.asarray(idx)[:qn]])
            parts_d.append(np.asarray(bd2, np.float32)[:qn])
        if not parts_ids:
            return (
                jnp.full((qn, k), -1, jnp.int32),
                jnp.full((qn, k), jnp.inf, jnp.float32),
            )
        ids = np.concatenate(parts_ids, axis=1)
        d2 = np.concatenate(parts_d, axis=1)
        # Tombstone masking stays host-side (the dense alive mask is numpy);
        # the dedup + rank + pad tail is the shared associative merge — the
        # same `merge_topk` the sharded index uses across shards.
        dead = ~self._alive[np.clip(ids, 0, max(self._next_id - 1, 0))]
        d2 = np.where(dead, np.inf, d2)
        pad = ((0, qp - qn), (0, 0))
        with dispatch_scope("lsm.merge"):
            ids, d2 = search_lib.merge_topk(
                jnp.asarray(np.pad(ids, pad, constant_values=-1), jnp.int32),
                jnp.asarray(np.pad(d2, pad, constant_values=np.inf),
                            jnp.float32),
                k=k,
            )
        return ids[:qn], d2[:qn]

    # -- values --------------------------------------------------------------

    def values_at(self, ids, fill=0) -> jax.Array:
        """Gather per-point values for search-result ids; -1 slots get fill."""
        return self._lsm.values_at(ids, fill=fill)

    def values_dense(self) -> jax.Array:
        """The dense by-external-id values array (stale rows where deleted)."""
        return self._lsm.values_dense()

    # -- adoption ------------------------------------------------------------

    @classmethod
    def from_index(
        cls,
        index: HilbertIndex,
        *,
        values: Optional[jax.Array] = None,
        buffer_capacity: int = 4096,
        max_segments: int = 8,
    ) -> "MutableHilbertIndex":
        """Adopt a prebuilt immutable index as segment 0 (ids = 0..n-1).

        If the index was built with ``store_points=False`` it can serve and
        absorb inserts/deletes, but compactions touching segment 0 raise
        (no raw points to re-sort).
        """
        self = cls(
            config=index.config,
            buffer_capacity=buffer_capacity,
            max_segments=max_segments,
        )
        n = index.n_points
        self._dim = index.dim
        self._buf_points = np.zeros((self.buffer_capacity, self._dim), np.float32)
        self._buf_ids = np.full((self.buffer_capacity,), -1, np.int32)
        self._next_id = n
        self._alive = np.ones((n,), np.bool_)
        if values is not None:
            vals = np.asarray(jax.device_get(values))
            if vals.shape[:1] != (n,):
                raise ValueError(f"values must be ({n}, ...)")
            self._values = vals.copy()
        # Pin the values mode now: a later insert(..., values=...) on a
        # valueless adoption would misalign the dense values array with the
        # already-assigned external ids 0..n-1.
        self._track_values = values is not None
        self.segments = [
            Segment(index=index, ids=np.arange(n, dtype=np.int32), gen=0)
        ]
        self._gen = 1
        return self

    # -- persistence ---------------------------------------------------------

    def save(self, path: str, *, kind: str = _DEFAULT_KIND,
             extra_meta: Optional[Dict] = None) -> str:
        return save_mutable_bundle(self, path, kind=kind, extra_meta=extra_meta)

    @classmethod
    def load(cls, path: str, *, kind: str = _DEFAULT_KIND
             ) -> "MutableHilbertIndex":
        index, _ = load_mutable_bundle(path, kind=kind)
        return index


def save_mutable_bundle(
    index: MutableHilbertIndex,
    path: str,
    *,
    kind: str = _DEFAULT_KIND,
    extra_meta: Optional[Dict] = None,
) -> str:
    """Persist a mutable index as segment bundles + state bundle + manifest.

    Each piece is an atomic ``repro.checkpoint`` bundle and NOTHING a
    previous manifest references is ever rewritten in place: segments are
    immutable and keyed by generation (an existing bundle with a matching
    uid is skipped, so repeated saves only write what changed) and the
    mutable buffer/tombstone state goes to a FRESH step each save, with the
    step recorded in the manifest.  The top-level JSON manifest is renamed
    into place LAST, so a crash mid-save — or a concurrent load in another
    worker — always observes a complete, mutually consistent
    (manifest, bundles) pair.

    After the manifest commits, bundles referenced by neither the new nor
    the immediately-previous manifest are pruned (writers are assumed
    single; readers get one manifest generation of grace), so repeated
    saves to one path occupy bounded disk.
    """
    os.makedirs(path, exist_ok=True)
    prev_manifest = {}
    try:
        with open(os.path.join(path, _MANIFEST)) as f:
            prev_manifest = json.load(f)
    except (OSError, ValueError):
        pass
    seg_names = []
    for seg in index.segments:
        name = f"seg_{seg.gen:06d}"
        seg_dir = os.path.join(path, "segments", name)
        # Content-addressed dedup: only skip the write when the bundle on
        # disk holds exactly this segment's ids+codes (a different index
        # saved to the same path therefore can never leave stale data).
        uid = seg.content_uid()
        if _segment_bundle_uid(seg_dir) != uid:
            save_index_bundle(
                seg.index,
                seg_dir,
                kind=_SEGMENT_KIND,
                extra_arrays={"ids": jnp.asarray(seg.ids)},
                extra_meta={"segment_uid": uid, "n_valid": seg.n_real},
            )
        seg_names.append(name)
    # Buffer state: the raw occupied slice, tombstoned rows included.
    # Keeping dead rows makes load() reconstruct the in-memory state
    # EXACTLY (same buffer occupancy, so later flush boundaries fall at
    # the same ops) — the invariant WAL recovery's bit-equality rests on.
    # Dead rows still drop for good at the next flush, as before.
    d = index._dim if index._dim is not None else 0
    bids = (index._buf_ids[: index._buf_count].copy()
            if index._buf_count else np.zeros((0,), np.int32))
    bpts = (index._buf_points[: index._buf_count].copy()
            if index._buf_count else np.zeros((0, d), np.float32))
    state: Dict[str, np.ndarray] = {
        "alive": index._alive,
        "buffer_points": bpts,
        "buffer_ids": bids,
    }
    if index._values is not None:
        state["values"] = index._values
    state_dir = os.path.join(path, "state")
    state_step = (checkpoint.latest_step(state_dir) or 0) + 1
    checkpoint.save(state_dir, step=state_step, tree=state, extra={})
    manifest = {
        "state_step": state_step,
        "kind": kind,
        "format_version": 1,
        "config": index.config.to_dict(),
        "buffer_capacity": index.buffer_capacity,
        "max_segments": index.max_segments,
        "next_id": int(index._next_id),
        "gen": int(index._gen),
        "dim": index._dim,
        "track_values": index._track_values,
        "segments": seg_names,
        "extra_meta": extra_meta or {},
    }
    fault_point("mutable.save.pre_manifest", path=os.path.join(path, _MANIFEST))
    checkpoint.atomic_write_json(os.path.join(path, _MANIFEST), manifest)
    _prune_unreferenced(path, manifest, prev_manifest)
    # The manifest now covers every acknowledged write: the WAL's records
    # are redundant and the log restarts empty.  A crash BETWEEN the
    # commit and this truncate only means records replay onto state that
    # already contains them — their next_id watermarks make that a no-op.
    if index._wal is not None:
        index._wal.truncate()
    return path


def _prune_unreferenced(path: str, manifest: Dict, prev_manifest: Dict) -> None:
    """Drop bundles neither the new nor the previous manifest references."""
    keep_segs = set(manifest["segments"]) | set(prev_manifest.get("segments", []))
    seg_root = os.path.join(path, "segments")
    if os.path.isdir(seg_root):
        for name in os.listdir(seg_root):
            if name.startswith("seg_") and name not in keep_segs:
                shutil.rmtree(os.path.join(seg_root, name), ignore_errors=True)
    checkpoint.prune_steps(
        os.path.join(path, "state"),
        {manifest["state_step"], prev_manifest.get("state_step")},
    )


def _segment_bundle_uid(seg_dir: str) -> Optional[str]:
    """uid of an already-saved segment bundle, or None if absent/unreadable."""
    step = checkpoint.latest_step(seg_dir)
    if step is None:
        return None
    try:
        with open(os.path.join(seg_dir, f"step_{step:08d}",
                               "manifest.json")) as f:
            return json.load(f).get("extra", {}).get("segment_uid")
    except (OSError, ValueError):
        return None


def _restore_state_bundle(path: str, step: Optional[int]
                          ) -> Dict[str, np.ndarray]:
    """Load every leaf of a checkpoint bundle with manifest-declared dtypes."""
    if step is None:  # pre-state_step manifests: newest available
        step = checkpoint.latest_step(path)
    if step is None:
        raise FileNotFoundError(f"no state bundle under {path!r}")
    with open(os.path.join(path, f"step_{step:08d}", "manifest.json")) as f:
        manifest = json.load(f)
    abstract = {}
    for key, (_, dtype_str) in manifest["leaves"].items():
        abstract[key[2:-2]] = jax.ShapeDtypeStruct((0,), np.dtype(dtype_str))
    arrays, _ = checkpoint.restore(path, step, abstract)
    # np.array (not asarray): device_get hands back read-only views, and
    # this state is mutated in place by post-restore deletes/WAL replay
    return {k: np.array(jax.device_get(v)) for k, v in arrays.items()}


def load_mutable_bundle(
    path: str, *, kind: str = _DEFAULT_KIND
) -> Tuple[MutableHilbertIndex, Dict]:
    """Inverse of :func:`save_mutable_bundle`; returns (index, extra_meta)."""
    mpath = os.path.join(path, _MANIFEST)
    if not os.path.exists(mpath):
        raise FileNotFoundError(f"no mutable-index manifest under {path!r}")
    with open(mpath) as f:
        manifest = json.load(f)
    if manifest.get("kind") != kind:
        raise ValueError(
            f"{path!r} is not a mutable-index checkpoint of kind {kind!r} "
            f"(kind={manifest.get('kind')!r})"
        )
    index = MutableHilbertIndex(
        config=IndexConfig.from_dict(manifest["config"]),
        buffer_capacity=int(manifest["buffer_capacity"]),
        max_segments=int(manifest["max_segments"]),
    )
    for name in manifest["segments"]:
        seg_index, extras, seg_meta = load_index_bundle(
            os.path.join(path, "segments", name), kind=_SEGMENT_KIND
        )
        index.segments.append(
            Segment(
                index=seg_index,
                ids=np.asarray(jax.device_get(extras["ids"]), np.int32),
                gen=int(name.split("_")[1]),
                n_valid=int(seg_meta.get("n_valid", -1)),
            )
        )
    state = _restore_state_bundle(
        os.path.join(path, "state"), manifest.get("state_step")
    )
    index._alive = np.asarray(state["alive"], np.bool_)
    index._next_id = int(manifest["next_id"])
    index._gen = int(manifest["gen"])
    index._track_values = manifest.get("track_values")
    if "values" in state:
        index._values = state["values"]
    dim = manifest.get("dim")
    if dim is not None:
        index._dim = int(dim)
        index._buf_points = np.zeros((index.buffer_capacity, index._dim),
                                     np.float32)
        index._buf_ids = np.full((index.buffer_capacity,), -1, np.int32)
        bpts, bids = state["buffer_points"], state["buffer_ids"]
        m = int(bids.shape[0])
        if m:
            index._buf_points[:m] = bpts
            index._buf_ids[:m] = bids
        index._buf_count = m
    _recover_wal(index, path)
    return index, manifest.get("extra_meta", {})


def _recover_wal(index: MutableHilbertIndex, path: str) -> None:
    """Replay + re-attach ``<path>/wal.log`` if the index was WAL-enabled.

    Replays the acknowledged tail (everything since the manifest last
    truncated the log) in original order on top of the restored state,
    then re-attaches the log so durability stays on.  Records whose
    ``next_id`` watermark the restored state already covers are skipped —
    the crash-between-commit-and-truncate window.
    """
    wfile = wal_lib.wal_path(path)
    if not os.path.exists(wfile):
        return
    records, wal = wal_lib.open_and_recover(wfile)
    replay_wal_records(index, records)
    index._wal = wal


def replay_wal_records(index, records) -> int:
    """Apply WAL records to a WAL-less index; returns ops applied.

    Shared by both mutable facades (they expose the same insert/
    bulk_load/delete and ``_lsm``).  The caller must not have a WAL
    attached yet, or the replay would re-log itself.
    """
    if getattr(index, "_wal", None) is not None:
        raise ValueError("detach the WAL before replaying records into it")
    applied = 0
    for rec in records:
        if rec.op in ("insert", "bulk_load"):
            wm = rec.meta.get("next_id")
            if wm is not None and wm < index._lsm.next_id:
                continue  # the restored checkpoint already contains it
            vals = rec.arrays.get("values")
            if rec.op == "bulk_load":
                index.bulk_load(rec.arrays["points"], vals)
            else:
                index.insert(rec.arrays["points"], vals)
        elif rec.op == "delete":
            # Idempotent: re-deleting checkpoint-covered ids is a no-op.
            index.delete(rec.arrays["ids"])
        else:
            raise wal_lib.WalError(f"unknown WAL op {rec.op!r}")
        applied += 1
    return applied
