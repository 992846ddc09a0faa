"""ShardedHilbertIndex: the row-partitioned Hilbert forest, end to end.

One host's RAM stops being the index capacity ceiling here: the corpus is
row-partitioned across the mesh's ``data`` axis, each device holds ONE
shard's complete index state (forest arrays, sketches, nibble-packed
codes — a full per-shard :class:`HilbertIndex` worth of arrays), and
search / checkpointing / serving all understand the partitioned layout.

Layout
  The partition is **contiguous runs of the master Hilbert order**
  (:func:`repro.core.distributed.hilbert_partition`, the sample sort at
  multi-device scale): shard ``s`` owns the ``s``-th stretch of the global
  curve walk, so its rows are a locality-tight curve segment — the
  hyperorthogonal well-folded curve argument for why a per-shard top-k
  merge loses little recall.  Every shard is padded to equal length with
  cyclic copies of its own rows (fully-empty shards with copies of global
  row 0); padding rows keep their REAL global ids, so they surface as
  duplicate ids and the cross-shard merge's dedup collapses them — no
  special sentinel rows exist anywhere in the hot path.

Search
  ONE jitted dispatch per query chunk: inside ``shard_map`` (queries
  replicated, rows sharded) each device runs PR 3's
  :func:`repro.core.search.fused_search_chunk` over its shard, maps local
  hits to global ids, **deflates** its inflated candidate pool to a true
  local top-k, and the shards reduce via
  :func:`repro.core.distributed.cross_shard_merge_topk`: by default a
  butterfly tree reduction of the associative
  :func:`repro.core.search.merge_topk` — log2(S) ``ppermute`` hops, each
  exchanging exactly k rows per query (``merge="tree"``, auto-selected on
  power-of-two shard counts), optionally preceded by a ``pmin``
  distance-bound prune (``merge_prune``).  The flat
  ``all_gather``-everything + one ``merge_topk`` path survives bit-exact
  as ``merge="gather"`` — the parity reference and the non-pow2
  fallback.  Every shard is searched for ``k + pad_max`` results
  (``pad_max`` = the largest padding count among non-empty shards, a
  static build-time int) so duplicate padding rows can never crowd a
  distinct neighbor out of the merge.

  All shards share ONE globally fit quantizer, so per-shard ADC distances
  dequantize against the same centroids: distances merged across shards
  are mutually comparable and equal to the single-device values for the
  same (query, point) pairs.  A 1-shard index skips the shard_map
  entirely and delegates to ``HilbertIndex.search(fused=True)`` —
  bit-identical to the single-device fused path by construction.

Checkpoints (format_version 3)
  ``save()`` writes one atomic per-shard bundle (an ordinary
  :func:`repro.index.facade.save_index_bundle`, so each shard is a valid
  v2 index checkpoint on its own) plus a top-level JSON manifest renamed
  into place last.  ``load()`` re-assembles the stacks when the target
  mesh matches the on-disk shard count, **reshards** (gathers points +
  ids, rebuilds at the new count with the SAME quantizer) when it does
  not, and adopts plain v2 single-index bundles the same way — changing
  the device count never invalidates a checkpoint.
"""

from __future__ import annotations

import dataclasses
import json
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import checkpoint
from repro.core import distributed as distributed_lib
from repro.core import forest as forest_lib
from repro.core import quantize
from repro.core import search as search_lib
from repro.core import sketch
from repro.core.types import SearchParams
from repro.index.config import IndexConfig
from repro.obs.dispatch import dispatch_scope
from repro.index.facade import (
    BoundedJitCache,
    HilbertIndex,
    _pow2_bucket,
    build_with_timings,
    load_index_bundle,
    resolve_backend,
    save_index_bundle,
)

__all__ = [
    "ShardedHilbertIndex",
    "ShardStack",
    "build_auto",
    "shard_index_from_stack",
    "stack_shard_indexes",
]

_SHARDED_MANIFEST = "sharded_manifest.json"
_SHARD_KIND = "sharded_index_shard"
_DEFAULT_KIND = "sharded_hilbert_index"
_FORMAT_VERSION = 3


def _data_mesh(n: Optional[int] = None) -> Mesh:
    from repro.launch.mesh import data_mesh

    return data_mesh(n)


class ShardStack(NamedTuple):
    """Per-shard index arrays stacked on a leading shard axis.

    Every leaf is ``(S, ...)`` and device_put with ``P('data')``, so device
    ``s`` physically holds only shard ``s``'s row — the per-device resident
    bytes of the big leaves are ``nbytes / S`` (verified by
    ``memory_report()``).  ``perms``/``flips`` are shared by all shards
    (same forest seed) and the quantizer is global, so those stay
    replicated outside the stack.
    """

    orders: jax.Array        # (S, T, n_pad) int32, per-tree Hilbert orders
    directories: jax.Array   # (S, T, n_dir, W) uint32 rank directories
    lo: jax.Array            # (S, d) float32 per-shard curve bounds
    hi: jax.Array            # (S, d) float32
    sketches: jax.Array      # (S, n_pad, Ws) uint32 (on TPU blocked), master
    codes: jax.Array         # (S, n_pad, Wc) uint32, nibble-packed, master
    master_order: jax.Array  # (S, n_pad) int32: position -> local row
    master_rank: jax.Array   # (S, n_pad) int32: local row -> position
    id_map: jax.Array        # (S, n_pad) int32: local row -> GLOBAL id


def stack_shard_indexes(
    mesh: Mesh,
    shard_indexes: List[HilbertIndex],
    id_maps: np.ndarray,           # (S, n_pad) int32 local row -> id
    *,
    store_points: bool,
) -> Tuple[ShardStack, Optional[jax.Array]]:
    """Stack per-shard :class:`HilbertIndex` leaves over the mesh.

    Returns ``(stack, points)`` with every leaf ``(S, ...)`` and laid out
    ``P('data')``.  ``id_maps`` may carry either global row ids (the static
    :class:`ShardedHilbertIndex`) or stable external ids (the sharded
    mutable facade's sealed generations) — the stack is agnostic; its
    ``id_map`` is simply what local search hits are gathered through.
    """
    data_sh = NamedSharding(mesh, P("data"))
    devices = list(mesh.devices.flat)

    def stack_leaf(get):
        # Assemble from per-device pieces: shard s moves (if at all) only to
        # device s, so no device ever stages the whole stack.
        parts = [
            jax.device_put(get(ix)[None], dev)
            for ix, dev in zip(shard_indexes, devices)
        ]
        shape = (len(parts),) + parts[0].shape[1:]
        return jax.make_array_from_single_device_arrays(shape, data_sh, parts)

    stack = ShardStack(
        orders=stack_leaf(lambda ix: ix.forest.orders),
        directories=stack_leaf(lambda ix: ix.forest.directories),
        lo=stack_leaf(lambda ix: ix.forest.lo),
        hi=stack_leaf(lambda ix: ix.forest.hi),
        sketches=stack_leaf(lambda ix: ix.sketches_master),
        codes=stack_leaf(lambda ix: ix.codes_master),
        master_order=stack_leaf(lambda ix: ix.master_order),
        master_rank=stack_leaf(lambda ix: ix.master_rank),
        id_map=jax.device_put(np.asarray(id_maps, np.int32), data_sh),
    )
    points = stack_leaf(lambda ix: ix.points) if store_points else None
    return stack, points


def shard_index_from_stack(
    config: IndexConfig,
    stack: ShardStack,
    points: Optional[jax.Array],
    quant: quantize.Quantizer,
    perms: jax.Array,
    flips: jax.Array,
    s: int,
) -> HilbertIndex:
    """Shard ``s``'s slice of a stack as a self-contained v2 HilbertIndex.

    The inverse of :func:`stack_shard_indexes` for one shard — used by both
    sharded checkpoint writers (static v3, mutable v4) so every per-shard
    bundle on disk is an ordinary loadable index checkpoint.
    """
    return HilbertIndex(
        config=dataclasses.replace(config, shards=None),
        forest=forest_lib.HilbertForest(
            perms=perms, flips=flips,
            orders=jnp.asarray(np.asarray(stack.orders[s])),
            directories=jnp.asarray(np.asarray(stack.directories[s])),
            lo=jnp.asarray(np.asarray(stack.lo[s])),
            hi=jnp.asarray(np.asarray(stack.hi[s])),
        ),
        quant=quant,
        codes_master=jnp.asarray(np.asarray(stack.codes[s])),
        sketches_master=jnp.asarray(np.asarray(stack.sketches[s])),
        master_order=jnp.asarray(np.asarray(stack.master_order[s])),
        master_rank=jnp.asarray(np.asarray(stack.master_rank[s])),
        points=(
            None if points is None else jnp.asarray(np.asarray(points[s]))
        ),
    )


@dataclasses.dataclass
class ShardedHilbertIndex:
    """Row-partitioned Hilbert forest over the mesh's ``data`` axis."""

    config: IndexConfig
    mesh: Mesh
    quant: quantize.Quantizer          # global (replicated)
    perms: jax.Array                   # (T, d) shared forest randomization
    flips: jax.Array                   # (T, d)
    stack: Optional[ShardStack]        # None iff n_shards == 1
    points: Optional[jax.Array]        # (S, n_pad, d) iff store_points
    single: Optional[HilbertIndex]     # the 1-shard fast path
    n_points: int
    n_valid: np.ndarray                # (S,) rows actually owned per shard
    pad_max: int                       # largest pad count among non-empty shards

    def __post_init__(self):
        self._chunk_fns = BoundedJitCache()
        self.last_dispatch_count = 0

    # -- introspection -------------------------------------------------------

    @property
    def n_shards(self) -> int:
        return int(self.mesh.shape["data"]) if self.single is None else 1

    @property
    def n_pad(self) -> int:
        return (
            self.single.n_points if self.single is not None
            else int(self.stack.id_map.shape[1])
        )

    @property
    def dim(self) -> int:
        return self.quant.boundaries.shape[0]

    def memory_report(self) -> Dict[str, object]:
        """The paper's RAM model plus the partitioned-layout actuals.

        ``per_device_bytes`` is what one device/host must actually hold:
        its slice of every sharded leaf plus a copy of every replicated
        leaf — for the big leaves that is ``total / n_shards``, which is
        the whole point of the partition (the paper's 16 GB single-box
        accounting divided across the mesh, plus the small replicated
        quantizer/randomization overhead).
        """
        if self.single is not None:
            rep = dict(self.single.memory_report())
            rep.update(
                n_shards=1,
                sharded_bytes=0,
                replicated_bytes=rep["resident_bytes"],
                per_device_bytes=[rep["resident_bytes"]],
            )
            return rep
        s = self.n_shards
        sharded_leaves = list(self.stack) + (
            [self.points] if self.points is not None else []
        )
        sharded = sum(int(leaf.nbytes) for leaf in sharded_leaves)
        replicated = sum(
            int(leaf.nbytes)
            for leaf in (self.quant.boundaries, self.quant.centroids,
                         self.perms, self.flips)
        )
        rep = search_lib.paper_memory_model(
            self.n_points,
            self.dim,
            self.n_points * sketch.sketch_words(self.dim) * 4,
            int(self.stack.orders.nbytes + self.stack.directories.nbytes
                + self.perms.nbytes + self.flips.nbytes),
        )
        rep.update(
            n_shards=s,
            points_bytes=0 if self.points is None else int(self.points.nbytes),
            codes_bytes=int(self.stack.codes.nbytes),
            sketch_table_bytes=int(self.stack.sketches.nbytes),
            sharded_bytes=sharded,
            replicated_bytes=replicated,
            resident_bytes=sharded + replicated,
            total_bytes=sharded + replicated,
            per_device_bytes=[sharded // s + replicated] * s,
        )
        return rep

    def __repr__(self) -> str:
        rep = self.memory_report()
        return (
            f"ShardedHilbertIndex(n_points={self.n_points}, dim={self.dim}, "
            f"n_shards={self.n_shards}, n_pad={self.n_pad}, "
            f"per_device={rep['per_device_bytes'][0] / 1e6:.2f} MB, "
            f"total={rep['resident_bytes'] / 1e6:.2f} MB)"
        )

    # -- build ---------------------------------------------------------------

    @classmethod
    def build(
        cls,
        points: jax.Array,
        config: Optional[IndexConfig] = None,
        *,
        mesh: Optional[Mesh] = None,
    ) -> "ShardedHilbertIndex":
        """Partition rows over the mesh's ``data`` axis and build every shard.

        Args:
          points: (n, d) fp32 corpus; global row ids are ``0..n-1``.
          config: build configuration (``None`` = ``IndexConfig()``).
          mesh: explicit ``('data',)`` mesh; default derives one from
            ``config.shards`` (else every local device).

        Returns:
          The partitioned index; per-shard Algorithm-1 preprocessing runs
          once per shard over its contiguous master-curve run.

        The shard count is ``config.shards`` if set, else the mesh's
        ``data`` axis size (default mesh: every local device).  The
        quantizer is fit ONCE on the full corpus and shared by all shards.
        """
        if config is None:
            config = IndexConfig()
        pts = np.asarray(jax.device_get(points), np.float32)
        n = pts.shape[0]
        if n == 0:
            raise ValueError("cannot build a sharded index over 0 points")
        if mesh is None:
            mesh = _data_mesh(config.shards)
        n_shards = int(mesh.shape["data"])
        if config.shards is not None and config.shards != n_shards:
            raise ValueError(
                f"config.shards={config.shards} != mesh 'data' axis size "
                f"{n_shards}; pass a matching mesh (launch.mesh.data_mesh)"
            )
        quant = quantize.fit(
            pts, bits=config.quantizer.bits,
            sample_limit=config.quantizer.sample_limit,
        )
        return cls._build_impl(pts, config, mesh, quant)

    @classmethod
    def _build_impl(
        cls,
        pts: np.ndarray,
        config: IndexConfig,
        mesh: Mesh,
        quant: quantize.Quantizer,
    ) -> "ShardedHilbertIndex":
        n = pts.shape[0]
        n_shards = int(mesh.shape["data"])
        if n_shards == 1:
            single, _ = build_with_timings(
                jnp.asarray(pts), config, quant=quant
            )
            return cls(
                config=config, mesh=mesh, quant=quant,
                perms=single.forest.perms, flips=single.forest.flips,
                stack=None, points=None, single=single,
                n_points=n, n_valid=np.asarray([n], np.int64), pad_max=0,
            )

        gid_slices = distributed_lib.hilbert_partition(
            pts, config.forest, mesh=mesh, n_shards=n_shards
        )
        n_pad = -(-n // n_shards)
        n_valid = np.asarray([len(g) for g in gid_slices], np.int64)
        # pad_max counts only shards that own rows: a fully-empty shard's
        # padding duplicates global row 0 (owned — and merged away — by
        # shard 0), so it can never crowd out a distinct neighbor.
        pad_max = int(max(
            (n_pad - v for v in n_valid if v > 0), default=0
        ))
        id_maps = np.zeros((n_shards, n_pad), np.int32)
        for s, gids in enumerate(gid_slices):
            if len(gids) == 0:
                gids_pad = np.zeros((n_pad,), np.int32)
            else:
                reps = -(-n_pad // len(gids))
                gids_pad = np.tile(np.asarray(gids, np.int32), reps)[:n_pad]
            id_maps[s] = gids_pad

        def build_shard(s: int) -> HilbertIndex:
            # Each shard is built on the device that will hold it, all at
            # once: a jitted program compiles per device, so one thread per
            # device overlaps the compiles as well as the builds.
            dev = mesh.devices.flat[s]
            with jax.default_device(dev):
                idx, _ = build_with_timings(
                    jax.device_put(pts[id_maps[s]], dev), config,
                    quant=jax.device_put(quant, dev),
                )
            return idx

        with ThreadPoolExecutor(max_workers=n_shards) as pool:
            shard_indexes = list(pool.map(build_shard, range(n_shards)))
        return cls._assemble(
            config, mesh, quant, shard_indexes, id_maps, n, n_valid, pad_max
        )

    @classmethod
    def _assemble(
        cls, config, mesh, quant, shard_indexes, id_maps, n, n_valid, pad_max
    ) -> "ShardedHilbertIndex":
        """Stack per-shard index leaves and lay them out over the mesh."""
        repl = NamedSharding(mesh, P())
        stack, points = stack_shard_indexes(
            mesh, shard_indexes, id_maps, store_points=config.store_points
        )
        return cls(
            config=config, mesh=mesh,
            quant=jax.device_put(quant, repl),
            perms=jax.device_put(shard_indexes[0].forest.perms, repl),
            flips=jax.device_put(shard_indexes[0].forest.flips, repl),
            stack=stack, points=points, single=None,
            n_points=n, n_valid=np.asarray(n_valid, np.int64),
            pad_max=pad_max,
        )

    # -- search --------------------------------------------------------------

    def _resolve_merge(
        self, merge: Optional[str], prune: Optional[bool]
    ) -> Tuple[str, bool]:
        """Per-call knobs default to the config; "auto" resolves by S."""
        merge = distributed_lib.resolve_merge(
            merge if merge is not None else self.config.merge, self.n_shards
        )
        if prune is None:
            prune = self.config.merge_prune
        return merge, bool(prune)

    def search(
        self,
        queries: jax.Array,
        params: SearchParams = SearchParams(),
        *,
        backend: str = "auto",
        query_chunk: Optional[int] = None,
        merge: Optional[str] = None,
        prune: Optional[bool] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        """Mesh-wide Algorithm-1 search.

        Args:
          queries: (Q, d) fp32 batch, replicated across the mesh.
          params: Algorithm-1 hyper-parameters (paper Table 1 names);
            each shard searches for ``k + pad_max`` candidates.
          backend: kernel routing for the per-shard fused pipeline.
          query_chunk: per-dispatch chunk cap (default
            ``config.query_chunk``).
          merge: cross-shard merge strategy, ``"auto"|"gather"|"tree"``
            (default ``config.merge``); see :class:`IndexConfig`.
          prune: distance-bound early pruning on the tree path (default
            ``config.merge_prune``).

        Returns:
          ``(ids (Q, k) int32, sq_distances (Q, k) float32)`` with GLOBAL
          row ids, distances ascending; shortfalls pad id -1 / +inf.

        One jitted dispatch per query chunk (``last_dispatch_count`` records
        the count for the most recent call): the whole shard_map — per-shard
        fused pipeline, gid mapping, shard-local deflation, cross-shard
        reduction — is one XLA computation.  Chunks are padded to
        power-of-two buckets exactly like ``HilbertIndex.search``.
        """
        merge, prune = self._resolve_merge(merge, prune)
        if self.single is not None:
            chunk = query_chunk or self.config.query_chunk
            self.last_dispatch_count = -(-queries.shape[0] // chunk)
            return self.single.search(
                queries, params, backend=backend, query_chunk=query_chunk,
                fused=True,
            )
        use_kernels = resolve_backend(backend) == "pallas"
        if query_chunk is None:
            query_chunk = self.config.query_chunk
        qn = queries.shape[0]
        self.last_dispatch_count = 0
        if qn == 0:
            return (
                jnp.zeros((0, params.k), jnp.int32),
                jnp.zeros((0, params.k), jnp.float32),
            )
        k_local = self._k_local(params)
        fn = self._chunk_fn(params, k_local, use_kernels, merge, prune)
        outs_i, outs_d = [], []
        for s in range(0, qn, query_chunk):
            q = queries[s : s + query_chunk]
            m = q.shape[0]
            bucket = _pow2_bucket(m, query_chunk)
            if bucket > m:
                q = jnp.pad(q, ((0, bucket - m), (0, 0)))
            with dispatch_scope("sharded.search"):
                ids, dists = fn(
                    q, self.stack, self.perms, self.flips, self.quant
                )
            self.last_dispatch_count += 1
            if bucket > m:
                ids, dists = ids[:m], dists[:m]
            outs_i.append(ids)
            outs_d.append(dists)
        return jnp.concatenate(outs_i), jnp.concatenate(outs_d)

    def search_local(
        self,
        queries: jax.Array,
        params: SearchParams = SearchParams(),
        *,
        backend: str = "auto",
        query_chunk: Optional[int] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        """Per-shard search WITHOUT the cross-shard reduction.

        Runs the identical shard_map core as :meth:`search` — fused
        per-shard pipeline, gid mapping, shard-local k deflation — but
        stops before any collective and returns the still-sharded
        ``(ids (S, Q, k), sq_distances (S, Q, k))`` stacks.  This is the
        in-situ "shard core" stage of the sharded path: what the
        benchmark's merge-tax guard compares the merged latency against,
        so the reduction cost is measured on the same dispatch shape
        rather than against a standalone single-shard run.
        """
        if self.single is not None:
            ids, d2 = self.single.search(
                queries, params, backend=backend, query_chunk=query_chunk,
                fused=True,
            )
            return ids[None], d2[None]
        use_kernels = resolve_backend(backend) == "pallas"
        if query_chunk is None:
            query_chunk = self.config.query_chunk
        qn = queries.shape[0]
        if qn == 0:
            z = jnp.zeros((self.n_shards, 0, params.k))
            return z.astype(jnp.int32), z.astype(jnp.float32)
        k_local = self._k_local(params)
        fn = self._chunk_fn(params, k_local, use_kernels, "local", False)
        outs_i, outs_d = [], []
        for s in range(0, qn, query_chunk):
            q = queries[s : s + query_chunk]
            m = q.shape[0]
            bucket = _pow2_bucket(m, query_chunk)
            if bucket > m:
                q = jnp.pad(q, ((0, bucket - m), (0, 0)))
            with dispatch_scope("sharded.search_local"):
                ids, dists = fn(
                    q, self.stack, self.perms, self.flips, self.quant
                )
            if bucket > m:
                ids, dists = ids[:, :m], dists[:, :m]
            outs_i.append(ids)
            outs_d.append(dists)
        return jnp.concatenate(outs_i, axis=1), jnp.concatenate(outs_d, axis=1)

    def _k_local(self, params: SearchParams) -> int:
        window = min(2 * params.h + 1, self.n_pad)
        return max(1, min(params.k + self.pad_max, params.k2 * window))

    def _chunk_fn(self, params: SearchParams, k_local: int, use_kernels: bool,
                  merge: str, prune: bool):
        key = (params.k1, params.k2, params.h, params.k, k_local, use_kernels,
               merge, prune)
        fn = self._chunk_fns.get(key)
        if fn is not None:
            return fn
        mesh = self.mesh
        fcfg = self.config.forest
        k1, k2, h, k = params.k1, params.k2, params.h, params.k
        n_shards = self.n_shards

        def shard_fn(q, st, perms, flips, quant):
            # shard_map keeps the sharded leading axis at local size 1.
            ids_l, d2 = search_lib.fused_search_chunk(
                q, st.orders[0], st.directories[0], st.lo[0], st.hi[0],
                perms, flips, st.master_rank[0], st.sketches[0], st.codes[0],
                st.master_order[0], quant,
                bits=fcfg.bits, key_bits=fcfg.key_bits,
                leaf_size=fcfg.leaf_size, k1=k1, k2=k2, h=h, k=k_local,
                use_kernels=use_kernels,
            )
            gids = jnp.where(
                ids_l >= 0, st.id_map[0][jnp.maximum(ids_l, 0)], -1
            )
            d2 = jnp.where(gids >= 0, d2, jnp.inf)
            if merge == "local":
                # search_local: deflate and stop pre-collective, sharded out.
                ids_k, d_k = search_lib.merge_topk(gids, d2, k=k)
                return ids_k[None], d_k[None]
            return distributed_lib.cross_shard_merge_topk(
                gids, d2, k=k, axis="data", axis_size=n_shards,
                merge=merge, prune=prune,
            )
        out_specs = (
            (P("data"), P("data")) if merge == "local"
            else (P(None, None), P(None, None))
        )
        fn = jax.jit(
            shard_map(
                shard_fn,
                mesh=mesh,
                in_specs=(P(None, None), P("data"), P(), P(), P()),
                out_specs=out_specs,
                check_vma=False,
            )
        )
        self._chunk_fns.put(key, fn)
        return fn

    # -- persistence ---------------------------------------------------------

    def _shard_index(self, s: int) -> Tuple[HilbertIndex, np.ndarray]:
        """Shard ``s`` as a self-contained v2 HilbertIndex (+ its gid map)."""
        if self.single is not None:
            return self.single, np.arange(self.n_points, dtype=np.int32)
        index = shard_index_from_stack(
            self.config, self.stack, self.points, self.quant,
            self.perms, self.flips, s,
        )
        return index, np.asarray(self.stack.id_map[s], np.int32)

    def save(self, path: str, *, kind: str = _DEFAULT_KIND,
             extra_meta: Optional[Dict] = None) -> str:
        """Persist as per-shard bundles under ONE atomically-renamed manifest.

        Each shard bundle is an ordinary atomic index checkpoint
        (`save_index_bundle`), written BEFORE the top-level manifest
        commits — a crash mid-save leaves any previous manifest (and the
        bundles it references) fully intact, and a concurrent loader never
        observes a half-written shard set.
        """
        os.makedirs(path, exist_ok=True)
        names = []
        for s in range(self.n_shards):
            index, gids = self._shard_index(s)
            name = f"shard_{s:05d}"
            save_index_bundle(
                index,
                os.path.join(path, "shards", name),
                kind=_SHARD_KIND,
                extra_arrays={"shard_gids": jnp.asarray(gids)},
                extra_meta={
                    "shard": s,
                    "n_shards": self.n_shards,
                    "n_valid": int(self.n_valid[s]),
                },
            )
            names.append(name)
        manifest = {
            "kind": kind,
            "format_version": _FORMAT_VERSION,
            "config": self.config.to_dict(),
            "n_shards": self.n_shards,
            "n_points": int(self.n_points),
            "dim": int(self.dim),
            "pad_max": int(self.pad_max),
            "shards": names,
            "extra_meta": extra_meta or {},
        }
        checkpoint.atomic_write_json(
            os.path.join(path, _SHARDED_MANIFEST), manifest
        )
        return path

    @classmethod
    def load(
        cls,
        path: str,
        *,
        mesh: Optional[Mesh] = None,
        kind: str = _DEFAULT_KIND,
    ) -> "ShardedHilbertIndex":
        """Load a v3 sharded checkpoint — or adopt/reshard a v2 single bundle.

        The target shard count is the mesh's ``data`` axis size (default
        mesh: every local device).  When it differs from the checkpoint's
        shard count, the index is RESHARDED on load: points + global ids
        are gathered from the stored shards and the partition is rebuilt at
        the new count with the checkpoint's own quantizer, so distances are
        unchanged.  Resharding needs stored points
        (``IndexConfig(store_points=True)``, the default).
        """
        if mesh is None:
            mesh = _data_mesh()
        target = int(mesh.shape["data"])
        mpath = os.path.join(path, _SHARDED_MANIFEST)
        if not os.path.exists(mpath):
            # v2 single-index bundle: adopt as 1 shard, reshard if needed.
            index, _, _ = load_index_bundle(path)
            config = dataclasses.replace(index.config, shards=None)
            if target == 1:
                return cls(
                    config=config, mesh=mesh, quant=index.quant,
                    perms=index.forest.perms, flips=index.forest.flips,
                    stack=None, points=None, single=index,
                    n_points=index.n_points,
                    n_valid=np.asarray([index.n_points], np.int64), pad_max=0,
                )
            if index.points is None:
                raise ValueError(
                    "cannot reshard a v2 bundle saved with store_points="
                    "False onto a multi-device mesh (no raw points to "
                    "re-partition)"
                )
            return cls._build_impl(
                np.asarray(jax.device_get(index.points), np.float32),
                config, mesh, index.quant,
            )
        with open(mpath) as f:
            manifest = json.load(f)
        if manifest.get("kind") != kind:
            raise ValueError(
                f"{path!r} is not a sharded-index checkpoint of kind "
                f"{kind!r} (kind={manifest.get('kind')!r})"
            )
        config = IndexConfig.from_dict(manifest["config"])
        n = int(manifest["n_points"])
        shard_indexes, id_maps, n_valid = [], [], []
        for name in manifest["shards"]:
            idx, extras, extra = load_index_bundle(
                os.path.join(path, "shards", name), kind=_SHARD_KIND
            )
            shard_indexes.append(idx)
            id_maps.append(np.asarray(jax.device_get(extras["shard_gids"]),
                                      np.int32))
            n_valid.append(int(extra["n_valid"]))
        if target == len(shard_indexes):
            if target == 1:
                return cls(
                    config=config, mesh=mesh, quant=shard_indexes[0].quant,
                    perms=shard_indexes[0].forest.perms,
                    flips=shard_indexes[0].forest.flips,
                    stack=None, points=None, single=shard_indexes[0],
                    n_points=n, n_valid=np.asarray(n_valid, np.int64),
                    pad_max=0,
                )
            return cls._assemble(
                config, mesh, shard_indexes[0].quant, shard_indexes,
                np.stack(id_maps), n, n_valid, int(manifest["pad_max"]),
            )
        # Shard-count change: gather owned rows, rebuild at the new count.
        if any(ix.points is None for ix in shard_indexes):
            raise ValueError(
                f"checkpoint has {len(shard_indexes)} shards but the mesh "
                f"wants {target}; resharding needs stored points "
                "(IndexConfig(store_points=True))"
            )
        pts = np.zeros((n, shard_indexes[0].dim), np.float32)
        for ix, gids, nv in zip(shard_indexes, id_maps, n_valid):
            own = gids[:nv]
            pts[own] = np.asarray(jax.device_get(ix.points))[: len(own)]
        # The checkpoint's config.shards describes the OLD partition; the
        # resharded index follows the mesh (auto), like the v2-adopt path.
        return cls._build_impl(
            pts, dataclasses.replace(config, shards=None), mesh,
            shard_indexes[0].quant,
        )


def build_auto(
    points: jax.Array,
    config: Optional[IndexConfig] = None,
    *,
    mesh: Optional[Mesh] = None,
    mutable: Optional[bool] = None,
    values: Optional[jax.Array] = None,
    buffer_capacity: int = 1024,
    max_segments: int = 8,
):
    """The ``backend="auto"`` of index construction.

    Args:
      points: (n, d) corpus to index.
      config: build configuration; ``None`` means ``IndexConfig()``.
      mesh: explicit ``('data',)`` mesh; default derives one from
        ``config.shards`` (else every local device).
      mutable: build the streaming (LSM) facade; ``None`` defers to
        ``config.mutable``.
      values: optional (n, ...) per-point payloads (mutable facades only).
      buffer_capacity: write-buffer rows (per shard when sharded);
        mutable facades only.
      max_segments: sealed-segment cap before tier merging; mutable only.

    Returns:
      The facade matching the resolved shard count (``config.shards``,
      else the mesh's ``data`` axis, else every local device) and
      mutability: :class:`HilbertIndex`, :class:`ShardedHilbertIndex`,
      :class:`repro.index.MutableHilbertIndex`, or
      :class:`repro.index.ShardedMutableHilbertIndex` — so the same call
      site scales from a laptop to a pod, static or streaming, without
      branching.
    """
    if config is None:
        config = IndexConfig()
    if mutable is None:
        mutable = config.mutable
    if mesh is not None:
        n_shards = int(mesh.shape["data"])
    elif config.shards is not None:
        n_shards = config.shards
    else:
        n_shards = jax.device_count()
    if n_shards > 1:
        if mutable:
            from repro.index.sharded_mutable import ShardedMutableHilbertIndex

            return ShardedMutableHilbertIndex.build(
                points, config, mesh=mesh, values=values,
                buffer_capacity=buffer_capacity, max_segments=max_segments,
            )
        return ShardedHilbertIndex.build(points, config, mesh=mesh)
    config = dataclasses.replace(config, shards=None)
    if mutable:
        from repro.index.mutable import MutableHilbertIndex

        mut = MutableHilbertIndex(
            config, buffer_capacity=buffer_capacity, max_segments=max_segments
        )
        mut.bulk_load(points, values)
        return mut
    return HilbertIndex.build(points, config)
