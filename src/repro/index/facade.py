"""HilbertIndex: the unified, self-describing Hilbert-forest index.

One artifact, three uses (paper: SISAP 2025 Tasks 1/2 + serving):

* ``HilbertIndex.build(points, cfg)`` — Task-1 preprocessing (quantizer,
  sketches, forest, master order) behind one call.
* ``.search(queries, params)`` — Algorithm-1 ANN search.  The index carries
  its build-time :class:`IndexConfig`, so no config argument exists to
  mismatch (the legacy API's silent-corruption footgun).
* ``.knn_graph(params)`` — Algorithm-2 graph construction **reusing** the
  already-fit quantizer/codes/sketches instead of re-fitting.
* ``.save(path)`` / ``HilbertIndex.load(path)`` — atomic persistence on the
  ``repro.checkpoint`` machinery; build once, load in many serving workers.

The class is a registered JAX pytree (arrays are children, the config is
static aux data), so an index can be passed through ``jax.jit``/``tree_map``
or device_put like any array bundle.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from collections import OrderedDict
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint
from repro.core import forest as forest_lib
from repro.core import knn_graph as knn_graph_lib
from repro.core import quantize, sketch
from repro.core import search as search_lib
from repro.core.types import GraphParams, SearchParams
from repro.index.config import IndexConfig
from repro.obs.dispatch import dispatch_scope
from repro.obs.registry import default_registry
from repro.obs.trace import span

__all__ = [
    "HilbertIndex",
    "BoundedJitCache",
    "build_with_timings",
    "resolve_backend",
    "save_index_bundle",
    "load_index_bundle",
]

_INF = jnp.int32(2**30)

BACKENDS = ("auto", "xla", "pallas")

# Leaf dtypes of the serialized array bundle (manifest-independent, so load
# never trusts dtypes from disk beyond a cast to these).  ``codes_master``
# is nibble-packed uint32 since format_version 2; v1 bundles stored it
# unpacked uint8 and are repacked transparently on load.
_FORMAT_VERSION = 2
_LEAF_DTYPES = {
    "forest.perms": jnp.int32,
    "forest.flips": jnp.bool_,
    "forest.orders": jnp.int32,
    "forest.directories": jnp.uint32,
    "forest.lo": jnp.float32,
    "forest.hi": jnp.float32,
    "quant.boundaries": jnp.float32,
    "quant.centroids": jnp.float32,
    "codes_master": jnp.uint32,
    "sketches_master": jnp.uint32,
    "master_order": jnp.int32,
    "master_rank": jnp.int32,
    "points": jnp.float32,
}


def _pow2_bucket(m: int, cap: int) -> int:
    """Smallest power of two >= m, capped at ``cap`` (the chunk size)."""
    b = 1
    while b < m and b < cap:
        b <<= 1
    return min(b, cap)


class BoundedJitCache:
    """LRU-bounded cache of compiled per-shape dispatch closures.

    The sharded facades key one jitted shard_map executable per
    (bucket, k, merge-knob, ...) tuple.  Keys recycle by construction in
    steady state (pow2 query buckets, pow2-padded seals), but a
    long-lived process that changes params or churns through segment
    layouts would otherwise accumulate one executable per *historical*
    shape forever.  Both ``ShardedHilbertIndex`` and
    ``ShardedMutableHilbertIndex`` share this bound: least-recently-used
    eviction at ``max_entries``, where a ``get`` hit refreshes recency.
    Eviction drops our reference to the closure; XLA frees the
    executable when the last reference dies.

    Thread-safe: the serving engine runs searches under a SHARED
    reader-writer lock, so concurrent readers hit this cache together.
    ``get``/``put`` are atomic under an internal mutex (a ``get`` hit
    mutates LRU recency — the one read-path mutation the facades keep,
    made safe here rather than pushed onto every caller).  Two racing
    misses may both compile; both closures are equivalent and the loser
    is simply dropped by ``put``'s overwrite.
    """

    def __init__(self, max_entries: int = 32):
        if max_entries < 1:
            raise ValueError(f"max_entries must be >= 1, got {max_entries}")
        self.max_entries = max_entries
        self._lock = threading.Lock()
        self._entries: "OrderedDict[tuple, object]" = OrderedDict()

    def get(self, key):
        with self._lock:
            fn = self._entries.get(key)
            if fn is not None:
                self._entries.move_to_end(key)
            return fn

    def put(self, key, fn) -> None:
        with self._lock:
            while len(self._entries) >= self.max_entries:
                self._entries.popitem(last=False)
            self._entries[key] = fn

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def keys(self) -> tuple:
        """Key-set snapshot (purity tests fingerprint THIS, not recency
        order — LRU refresh on a hit is deliberate and benign)."""
        with self._lock:
            return tuple(self._entries.keys())

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._entries


def resolve_backend(backend: str) -> str:
    """Kernel-routing policy: 'auto' → Pallas on TPU, XLA elsewhere."""
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return backend


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class HilbertIndex:
    """Self-describing Hilbert-forest index (config travels with the arrays)."""

    config: IndexConfig
    forest: forest_lib.HilbertForest
    quant: quantize.Quantizer
    codes_master: jax.Array  # (n, ceil(d/8)) uint32, nibble-PACKED, master order
    # Master order; on a TPU the blocked (ceil(n·S/128), 128) uint32 table
    # (each sketch in an S-word slot, 128/S per row), elsewhere (n, W)
    # uint32 rows (repro.core.sketch.resident_table).
    sketches_master: jax.Array
    master_order: jax.Array  # (n,) int32: position -> point id
    master_rank: jax.Array  # (n,) int32: point id -> position
    points: Optional[jax.Array] = None  # (n, d) fp32 iff config.store_points

    # -- pytree protocol (config is static; arrays are children) ------------

    def tree_flatten(self):
        children = (
            self.forest,
            self.quant,
            self.codes_master,
            self.sketches_master,
            self.master_order,
            self.master_rank,
            self.points,
        )
        return children, self.config

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(aux, *children)

    # -- introspection -------------------------------------------------------

    @property
    def n_points(self) -> int:
        return self.master_order.shape[0]

    @property
    def dim(self) -> int:
        # codes_master is packed, so its width is ceil(d/8); the quantizer
        # grid keeps the true dimensionality.
        return self.quant.boundaries.shape[0]

    def sketches_view(self) -> jax.Array:
        """The sketches as (n, W) uint32 rows in master order, read from
        the resident table (for graph construction and persistence)."""
        return sketch.unblock_sketches(
            self.sketches_master, self.n_points, sketch.sketch_words(self.dim)
        )

    def memory_report(self) -> Dict[str, int]:
        """Bytes by component: the paper's RAM-budget model plus actuals.

        The model fields (``quantized_bytes``/``combined_stage2_bytes``/…)
        come from :func:`repro.core.search.paper_memory_model` — the single
        shared accounting.  Since codes are RESIDENT nibble-packed, the
        model's ``quantized_bytes`` equals the actual ``codes_bytes``.  The
        model counts the sketches as the paper does, n·W words;
        ``sketch_table_bytes`` is the resident table's actual size (on a
        TPU, blocked: 16-word slots for W = 12).
        ``codes_bytes``/``order_bytes``/``quant_bytes`` are the arrays
        actually resident, and ``resident_bytes``/``total_bytes`` sum every
        pytree leaf so segment lists and serving deployments can budget
        real RAM.
        """
        d = self.dim
        resident = sum(
            int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
            for leaf in jax.tree_util.tree_leaves(self)
        )
        rep = search_lib.paper_memory_model(
            self.n_points,
            d,
            self.n_points * sketch.sketch_words(d) * 4,
            self.forest.memory_bytes(),
        )
        rep.update(
            {
                "sketch_table_bytes": self.sketches_master.nbytes,
                "points_bytes": 0 if self.points is None else self.n_points * d * 4,
                "codes_bytes": int(np.prod(self.codes_master.shape)) * 4,  # u32
                "order_bytes": self.master_order.nbytes + self.master_rank.nbytes,
                "quant_bytes": self.quant.boundaries.nbytes
                + self.quant.centroids.nbytes,
                "resident_bytes": resident,
                "total_bytes": resident,
            }
        )
        return rep

    def __repr__(self) -> str:
        mb = self.memory_report()["resident_bytes"] / 1e6
        return (
            f"HilbertIndex(n_points={self.n_points}, dim={self.dim}, "
            f"n_trees={self.forest.n_trees}, "
            f"store_points={self.points is not None}, "
            f"backend={jax.default_backend()}, {mb:.2f} MB)"
        )

    # -- build ---------------------------------------------------------------

    @classmethod
    def build(cls, points: jax.Array, config: Optional[IndexConfig] = None
              ) -> "HilbertIndex":
        """Full Task-1 preprocessing: quantize, sketch, forest, master order.

        The paper's §3.1 pipeline behind one call: fit the 4-bit shared-MSB
        quantizer, derive binary sketches, build ``n_trees`` randomized
        Hilbert trees, and store codes/sketches rearranged into the
        un-permuted master Hilbert order (the layout Algorithm 1's stage-2
        window expansion reads contiguously).

        Args:
          points: (n, d) fp32 corpus to index.
          config: build configuration; ``None`` means ``IndexConfig()`` (a
            ``None`` sentinel, not a default-argument instance, so no
            config object is ever shared between calls).

        Returns:
          A self-describing index; its search never takes a config again.
        """
        index, _ = build_with_timings(points, config)
        return index

    # -- Task 1: Algorithm-1 search -----------------------------------------

    def search(
        self,
        queries: jax.Array,
        params: SearchParams = SearchParams(),
        *,
        backend: str = "auto",
        query_chunk: Optional[int] = None,
        fused: bool = True,
    ) -> Tuple[jax.Array, jax.Array]:
        """Batched search — the paper's Algorithm 1 (forest candidates →
        sketch Hamming filter → ±h master-order expansion → ADC → top-k).

        Args:
          queries: (Q, d) fp32 query batch.
          params: Algorithm-1 hyper-parameters (``k1``/``k2``/``h``/``k``,
            paper Table 1 names).
          backend: kernel routing, one of ``BACKENDS``.
          query_chunk: per-dispatch chunk cap (default
            ``config.query_chunk``).
          fused: take the single-dispatch fused path (default) or the
            bit-identical per-tree reference loop.

        Returns:
          ``(ids (Q, k) int32, sq_distances (Q, k) float32)``, distances
          ascending; with fewer than ``k`` points the tail is id ``-1`` /
          ``+inf``.

        No config argument: the forest/quantizer settings used at build time
        travel on ``self.config``.  ``backend`` routes the kernel stages
        (stage-1 Hamming filter + packed stage-2 ADC): ``"pallas"`` uses the
        Mosaic kernels (interpret-mode on CPU), ``"xla"`` the jnp oracles,
        ``"auto"`` picks Pallas only on TPU.

        ``query_chunk`` (default ``config.query_chunk``) caps the chunk
        size; every chunk is padded up to a power-of-two bucket (≤ the cap)
        and trimmed after, so a serving process sees at most
        ``log2(query_chunk)+1`` jit traces no matter how batch sizes vary —
        previously every distinct batch size below the chunk size triggered
        a fresh trace.

        ``fused=True`` (the hot path) runs one XLA dispatch per chunk via
        :func:`repro.core.search.fused_search_chunk`; ``fused=False`` keeps
        the per-tree dispatch loop + unpacked stage 2 as a bit-identical
        reference for parity tests and benchmarks.
        """
        use_kernels = resolve_backend(backend) == "pallas"
        if query_chunk is None:
            query_chunk = self.config.query_chunk
        qn = queries.shape[0]
        if qn == 0:  # idle decode step: no chunks, well-typed empty result
            return (
                jnp.zeros((0, params.k), jnp.int32),
                jnp.zeros((0, params.k), jnp.float32),
            )
        # Reference path: unpack the codes ONCE per search, not per chunk.
        codes_u8 = (
            None if fused
            else quantize.unpack_codes(self.codes_master, self.dim)
        )
        outs_i, outs_d = [], []
        for s in range(0, qn, query_chunk):
            q = queries[s : s + query_chunk]
            m = q.shape[0]
            bucket = _pow2_bucket(m, query_chunk)
            if bucket > m:
                q = jnp.pad(q, ((0, bucket - m), (0, 0)))
            with dispatch_scope("hilbert.search"):
                ids, dists = self._search_chunk(q, params, use_kernels,
                                                fused, codes_u8)
            if bucket > m:
                ids, dists = ids[:m], dists[:m]
            outs_i.append(ids)
            outs_d.append(dists)
        return jnp.concatenate(outs_i), jnp.concatenate(outs_d)

    def _search_chunk(self, queries, params: SearchParams, use_kernels: bool,
                      fused: bool = True, codes_u8=None):
        fcfg = self.config.forest
        f = self.forest
        if fused:
            return search_lib.fused_search_chunk(
                queries, f.orders, f.directories, f.lo, f.hi, f.perms, f.flips,
                self.master_rank, self.sketches_master, self.codes_master,
                self.master_order, self.quant,
                bits=fcfg.bits, key_bits=fcfg.key_bits,
                leaf_size=fcfg.leaf_size, k1=params.k1, k2=params.k2,
                h=params.h, k=params.k, use_kernels=use_kernels,
            )
        # Reference path: one dispatch per tree + stage 2 on codes unpacked
        # back to (n, d) uint8.  Bit-identical to the fused path on XLA;
        # kept for parity tests and the search_path benchmark baseline.
        qn = queries.shape[0]
        qsk = sketch.make_sketches(self.quant, queries)
        best_pos = jnp.full((qn, params.k2), -1, jnp.int32)
        best_dist = jnp.full((qn, params.k2), _INF, jnp.int32)
        for t in range(f.n_trees):
            best_pos, best_dist = search_lib.stage1_tree_merge(
                queries, qsk, best_pos, best_dist,
                f.orders[t], f.directories[t], f.lo, f.hi, f.perms[t], f.flips[t],
                self.master_rank, self.sketches_master,
                bits=fcfg.bits, key_bits=fcfg.key_bits,
                leaf_size=fcfg.leaf_size, k1=params.k1, k2=params.k2,
                use_kernels=use_kernels,
            )
        if codes_u8 is None:
            codes_u8 = quantize.unpack_codes(self.codes_master, self.dim)
        return search_lib.stage2_expand_rank(
            queries, best_pos, codes_u8, self.master_order, self.quant,
            h=params.h, k=params.k,
        )

    # -- Task 2: Algorithm-2 graph construction ------------------------------

    def knn_graph(
        self,
        params: GraphParams = GraphParams(),
        *,
        chunk: int = 1 << 14,
    ) -> Tuple[jax.Array, jax.Array]:
        """Approximate k-NN graph over the indexed points — the paper's
        Algorithm 2 (Task 2): repeated randomized Hilbert orders, ±k1
        neighbor windows, sketch-filtered running top-k2, exact re-rank.

        Args:
          params: Algorithm-2 hyper-parameters (``n_orders``/``k1``/
            ``k2``/``k``, paper Table 2 names).
          chunk: rows per jitted window pass (memory/speed knob only).

        Returns:
          ``(ids (n, k) int32, sq_distances (n, k) float32)`` — each
          indexed point's approximate k nearest neighbors, self excluded.

        Reuses the index's fitted quantizer → sketches and bounds instead of
        re-fitting from scratch (what the legacy ``build_knn_graph`` did).
        Requires ``config.store_points=True`` (default): the final exact
        re-ranking step needs the fp32 points.
        """
        if self.points is None:
            raise ValueError(
                "knn_graph() needs the raw points for exact re-ranking; this "
                "index was built with IndexConfig(store_points=False)"
            )
        # Sketches in point-id order, recovered from the master-order copy:
        # row master_rank[i] of the (n, W) view is point i's sketch.
        sketches_ids = self.sketches_view()[self.master_rank]
        fcfg = self.config.forest
        return knn_graph_lib.knn_graph_from_sketches(
            self.points, sketches_ids, params,
            bits=fcfg.bits, key_bits=fcfg.key_bits,
            lo=self.forest.lo, hi=self.forest.hi, chunk=chunk,
        )

    # -- persistence ---------------------------------------------------------

    def _array_bundle(self) -> Dict[str, jax.Array]:
        d = {
            "forest.perms": self.forest.perms,
            "forest.flips": self.forest.flips,
            "forest.orders": self.forest.orders,
            "forest.directories": self.forest.directories,
            "forest.lo": self.forest.lo,
            "forest.hi": self.forest.hi,
            "quant.boundaries": self.quant.boundaries,
            "quant.centroids": self.quant.centroids,
            "codes_master": self.codes_master,
            # On disk the sketches stay (n, W) whatever the device; load
            # lays them out for its own.
            "sketches_master": self.sketches_view(),
            "master_order": self.master_order,
            "master_rank": self.master_rank,
        }
        if self.points is not None:
            d["points"] = self.points
        return d

    def save(self, path: str) -> str:
        """Atomically persist index arrays + config under ``path``.

        Uses the ``repro.checkpoint`` machinery (tmp-dir + fsync + rename),
        so a crash mid-save can never corrupt a previously saved index and
        many serving workers can load concurrently.  Returns the final
        checkpoint directory.
        """
        return save_index_bundle(self, path)

    @classmethod
    def load(cls, path: str) -> "HilbertIndex":
        """Load an index saved with :meth:`save`; fully self-describing."""
        index, _, _ = load_index_bundle(path)
        return index


def save_index_bundle(
    index: HilbertIndex,
    path: str,
    *,
    kind: str = "hilbert_index",
    extra_arrays: Optional[Dict[str, jax.Array]] = None,
    extra_meta: Optional[Dict] = None,
) -> str:
    """Persist an index plus optional sidecar arrays as ONE atomic bundle.

    Wrappers that pair an index with companion data (e.g. the serving
    ``RetrievalStore``'s values array) use this so a crash or concurrent
    load can never observe the index and its sidecars out of sync.
    """
    bundle = dict(index._array_bundle())
    for k, v in (extra_arrays or {}).items():
        if k in _LEAF_DTYPES:
            raise ValueError(f"extra array name {k!r} collides with an index leaf")
        bundle[k] = v
    extra = {
        "kind": kind,
        "format_version": _FORMAT_VERSION,
        "config": index.config.to_dict(),
        "has_points": index.points is not None,
        "n_points": int(index.n_points),
        "dim": int(index.dim),
        "extra_arrays": sorted((extra_arrays or {}).keys()),
    }
    for k in extra_meta or {}:
        if k in extra:
            raise ValueError(f"extra_meta key {k!r} collides with a reserved key")
    extra.update(extra_meta or {})
    # Fresh step per save with one generation of grace: if the newest
    # bundle is later found rotted (digest mismatch) it is quarantined
    # and loads fall back to the previous, still-verifiable step.
    prev = checkpoint.latest_step(path)
    step = 0 if prev is None else prev + 1
    final = checkpoint.save(path, step=step, tree=bundle, extra=extra)
    checkpoint.prune_steps(path, {step, prev})
    return final


def load_index_bundle(
    path: str, *, kind: str = "hilbert_index"
) -> Tuple[HilbertIndex, Dict[str, jax.Array], Dict]:
    """Inverse of :func:`save_index_bundle`.

    Returns ``(index, extra_arrays, manifest_extra)``; sidecar array dtypes
    come from the manifest, index leaf dtypes from the static schema.

    Resolution is corruption-aware: if the newest step fails digest
    verification mid-restore it is quarantined (``*.quarantine/``) and
    the next-newest step is tried, so a bit-flipped bundle degrades to
    the previous verifiable save instead of a crash or — worse — a
    silently wrong index.
    """
    last_err: Optional[checkpoint.CorruptBundleError] = None
    while True:
        step = checkpoint.latest_step(path)
        if step is None:
            if last_err is not None:
                raise last_err
            raise FileNotFoundError(f"no HilbertIndex checkpoint under {path!r}")
        try:
            return _load_index_bundle_step(path, step, kind=kind)
        except checkpoint.CorruptBundleError as e:
            # restore() has quarantined the step; retry resolves older.
            last_err = e


def _load_index_bundle_step(
    path: str, step: int, *, kind: str
) -> Tuple[HilbertIndex, Dict[str, jax.Array], Dict]:
    try:
        with open(os.path.join(path, f"step_{step:08d}", "manifest.json")) as f:
            manifest = json.load(f)
    except ValueError as e:
        quarantined = checkpoint.quarantine_step(path, step)
        raise checkpoint.CorruptBundleError(
            path, step, [f"manifest unparseable: {e}"], quarantined
        ) from e
    extra = manifest.get("extra", {})
    if extra.get("kind") != kind:
        raise ValueError(
            f"{path!r} is not a HilbertIndex checkpoint of kind {kind!r} "
            f"(kind={extra.get('kind')!r})"
        )
    config = IndexConfig.from_dict(extra["config"])
    fmt = int(extra.get("format_version", 1))
    names = list(_LEAF_DTYPES)
    if not extra.get("has_points", False):
        names.remove("points")
    abstract = {k: jax.ShapeDtypeStruct((0,), _LEAF_DTYPES[k]) for k in names}
    if fmt < 2:
        # v1 bundles stored codes unpacked (n, d) uint8; restore them in
        # that dtype and repack below (transparent layout upgrade).
        abstract["codes_master"] = jax.ShapeDtypeStruct((0,), jnp.uint8)
    extra_names = extra.get("extra_arrays", [])
    for k in extra_names:
        # manifest leaves are keyed by jax keystr: "['<name>']"
        _, dtype_str = manifest["leaves"][f"['{k}']"]
        abstract[k] = jax.ShapeDtypeStruct((0,), np.dtype(dtype_str))
    arrays, _ = checkpoint.restore(path, step, abstract)
    if fmt < 2:
        arrays["codes_master"] = quantize.pack_codes(arrays["codes_master"])
    index = HilbertIndex(
        config=config,
        forest=forest_lib.HilbertForest(
            perms=arrays["forest.perms"],
            flips=arrays["forest.flips"],
            orders=arrays["forest.orders"],
            directories=arrays["forest.directories"],
            lo=arrays["forest.lo"],
            hi=arrays["forest.hi"],
        ),
        quant=quantize.Quantizer(
            boundaries=arrays["quant.boundaries"],
            centroids=arrays["quant.centroids"],
        ),
        codes_master=arrays["codes_master"],
        sketches_master=sketch.resident_table(arrays["sketches_master"]),
        master_order=arrays["master_order"],
        master_rank=arrays["master_rank"],
        points=arrays.get("points"),
    )
    return index, {k: arrays[k] for k in extra_names}, extra


def build_with_timings(
    points: jax.Array, config: Optional[IndexConfig] = None,
    *, quant: Optional[quantize.Quantizer] = None,
) -> Tuple[HilbertIndex, Dict[str, float]]:
    """Build an index and return per-phase wall times (paper §3.2 split).

    Phases: ``quantization`` (fit+encode), ``sketches``, ``forest`` (the
    dominant cost — n_trees Hilbert sorts), ``master_sort``.  Each phase's
    time is also recorded, in ms, into the registry's
    ``index_build_ms{phase=...}``.

    ``quant`` may supply a pre-fit quantizer instead of fitting one from
    ``points``.  The sharded facade builds every shard with ONE globally
    fit quantizer this way: per-shard ADC distances then dequantize against
    the same centroids, so distances merged across shards are mutually
    comparable and equal to what a single-device index over the union
    would compute.
    """
    if config is None:
        config = IndexConfig()
    n, _ = points.shape
    qcfg, fcfg = config.quantizer, config.forest
    timings: Dict[str, float] = {}

    t0 = time.time()
    with span("build.quantization", rows=int(n)), dispatch_scope(
        "build.quantization"
    ):
        if quant is None:
            quant = quantize.fit(
                points, bits=qcfg.bits, sample_limit=qcfg.sample_limit
            )
        codes = quantize.encode(quant, points)
        jax.block_until_ready(codes)
    timings["quantization"] = time.time() - t0

    t0 = time.time()
    with span("build.sketches"), dispatch_scope("build.sketches"):
        sketches = sketch.sketches_from_codes(codes, bits=qcfg.bits)
        jax.block_until_ready(sketches)
    timings["sketches"] = time.time() - t0

    t0 = time.time()
    with span("build.forest", n_trees=fcfg.n_trees), dispatch_scope(
        "build.forest"
    ):
        f = forest_lib.build_forest(points, fcfg)
        jax.block_until_ready(f.orders)
    timings["forest"] = time.time() - t0

    # Master order: an un-permuted Hilbert sort; vectors/sketches rearranged.
    t0 = time.time()
    with span("build.master_sort"), dispatch_scope("build.master_sort"):
        master_order, _ = search_lib.hilbert_master_sort(
            points, fcfg, f.lo, f.hi
        )
        master_rank = jnp.zeros((n,), jnp.int32).at[master_order].set(
            jnp.arange(n, dtype=jnp.int32)
        )
        jax.block_until_ready(master_order)
    timings["master_sort"] = time.time() - t0
    reg = default_registry()
    for phase, seconds in timings.items():
        reg.latency("index_build_ms", capacity=1024, phase=phase).record(
            1000.0 * seconds)

    index = HilbertIndex(
        config=config,
        forest=f,
        quant=quant,
        # Resident layout is nibble-packed (paper: 0.5 B/dim); pack AFTER
        # the master reorder so window reads stay contiguous.
        codes_master=quantize.pack_codes(codes[master_order]),
        sketches_master=sketch.resident_table(sketches[master_order]),
        master_order=master_order,
        master_rank=master_rank,
        points=points if config.store_points else None,
    )
    return index, timings
