"""IndexConfig: the single build-time configuration for :class:`HilbertIndex`.

Composes the core ``ForestConfig`` / ``QuantizerConfig`` dataclasses into one
frozen (hashable — usable as jit static aux data) object that the index
carries for its whole life, including across ``save()``/``load()``.  The
dict round-trip below is what lands in the checkpoint manifest, so a loaded
index is self-describing: no caller ever re-supplies the build config.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

from repro.core.types import ForestConfig, QuantizerConfig

__all__ = ["IndexConfig"]


def _filter_fields(cls, d: Dict[str, Any]) -> Dict[str, Any]:
    """Keep only known dataclass fields (forward-compatible manifests)."""
    names = {f.name for f in dataclasses.fields(cls)}
    return {k: v for k, v in d.items() if k in names}


@dataclasses.dataclass(frozen=True)
class IndexConfig:
    """Everything needed to (re)build or interpret a :class:`HilbertIndex`.

    Attributes:
      forest: Hilbert-forest shape (trees, curve bits, key width, leaf size).
      quantizer: 4-bit shared-MSB quantizer settings.
      store_points: keep the raw fp32 points on the index.  Required for
        ``knn_graph()`` (Task-2 exact re-ranking); turn off for serving
        deployments where only Algorithm-1 search runs and RAM matters.
      query_chunk: default search chunk cap.  Chunks are padded to
        power-of-two buckets up to this cap, so a serving process compiles
        at most ``log2(query_chunk)+1`` traces across all batch sizes.
        Travels with the index so every serving worker shares the same
        trace-bucket policy; overridable per call via
        ``search(query_chunk=...)``.  The default 512 bounds a chunk's
        scratch at Table-1 row 1 (d=384, k2=370, h=2): the XLA route
        dequantizes every candidate window, ~5.6 GiB at 512 queries and
        4x that at 2048 — more than a 16 GB TPU v5e holds beside the
        index (``tests/test_tpu_compile.py`` checks the bound).
      shards: row-partition count for the sharded facade.  ``None`` (the
        default) means "auto": :func:`repro.index.build_auto` picks one
        shard per device on the mesh's ``data`` axis when more than one
        device is visible, else a plain single-device index.  ``1`` forces
        single-device even on a multi-device host.
      mutable: ask :func:`repro.index.build_auto` for the streaming (LSM)
        facade instead of the immutable one — a
        :class:`repro.index.MutableHilbertIndex` on one shard, a
        :class:`repro.index.ShardedMutableHilbertIndex` on several — so one
        config describes a deployment that must absorb inserts/deletes
        while serving.  Build-time only: it changes which facade wraps the
        arrays, never the arrays themselves.
      seal_pow2: pad LSM *seal* builds (flushes and tier merges, never
        ``compact()`` or bulk loads) up to power-of-two row counts by
        cyclically repeating real rows.  Steady-state churn then recycles
        a handful of segment shapes instead of minting a new one per
        seal, so the jitted search stops recompiling once warm — the
        recompile gauge assert in ``benchmarks/churn.py``.  Costs a
        bounded amount of redundant rows (< 2x) and a matching top-k
        inflation; results stay exact w.r.t. the live rows.
      merge: cross-shard top-k merge strategy for the sharded facades.
        ``"gather"`` is the flat reference path (one ``all_gather`` of
        every shard's inflated candidate pool, one ``merge_topk``);
        ``"tree"`` is the butterfly reduction (log2(S) ``ppermute`` hops
        exchanging exactly k rows per query per hop — see
        :func:`repro.core.distributed.cross_shard_merge_topk`), which
        requires a power-of-two shard count; ``"auto"`` (the default)
        picks ``"tree"`` when the shard count is a power of two and
        falls back to ``"gather"`` otherwise.  The two paths return the
        same results (sorted distances bit-equal; ids equal up to
        distance ties).  Overridable per call via ``search(merge=...)``.
      merge_prune: with the tree merge, additionally exchange each
        shard's local kth-best distance (one ``pmin``) before the first
        hop and mask candidates that provably cannot enter the global
        top-k.  Exact — pruned entries are strictly worse than the
        global kth-best, so even tie order is unchanged — but one more
        collective; off by default.  Overridable via
        ``search(prune=...)``.
    """

    forest: ForestConfig = ForestConfig()
    quantizer: QuantizerConfig = QuantizerConfig()
    store_points: bool = True
    query_chunk: int = 512
    shards: Optional[int] = None
    mutable: bool = False
    seal_pow2: bool = False
    merge: str = "auto"
    merge_prune: bool = False

    def to_dict(self) -> Dict[str, Any]:
        """Manifest form of the config (the checkpoint round-trip).

        Returns:
          A plain-JSON dict with one key per field; nested configs become
          nested dicts.  ``from_dict(to_dict(cfg)) == cfg`` exactly.
        """
        return {
            "forest": dataclasses.asdict(self.forest),
            "quantizer": dataclasses.asdict(self.quantizer),
            "store_points": self.store_points,
            "query_chunk": self.query_chunk,
            "shards": self.shards,
            "mutable": self.mutable,
            "seal_pow2": self.seal_pow2,
            "merge": self.merge,
            "merge_prune": self.merge_prune,
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "IndexConfig":
        """Inverse of :meth:`to_dict`; tolerant of older/newer manifests.

        Unknown keys are dropped and missing keys take the field defaults,
        so manifests written by earlier format versions (which e.g. lack
        ``mutable``) and later ones (which may add fields) both load.
        """
        shards = d.get("shards")
        return cls(
            forest=ForestConfig(**_filter_fields(ForestConfig, d.get("forest", {}))),
            quantizer=QuantizerConfig(
                **_filter_fields(QuantizerConfig, d.get("quantizer", {}))
            ),
            store_points=bool(d.get("store_points", True)),
            query_chunk=int(d.get("query_chunk", 512)),
            shards=None if shards is None else int(shards),
            mutable=bool(d.get("mutable", False)),
            seal_pow2=bool(d.get("seal_pow2", False)),
            merge=str(d.get("merge", "auto")),
            merge_prune=bool(d.get("merge_prune", False)),
        )
