#!/usr/bin/env python3
"""Smoke run of the Hilbert-forest index on one TPU (or, with ``--chips 4``,
its sharded layout on a four-chip mesh).

It drives the path a SISAP deployment uses, through the entry points a user
calls, on a PUBMED23-shaped corpus cut only in row count: N = 2^20 rows of
d = 384 float32, made from ``--seed`` by
``repro.data.ann_datasets.lowrank_dataset_with_queries`` together with
1,000 held-out queries.  The index is the paper's
``configs/pubmed23.FOREST`` (160 trees, leaf 100, 448-bit keys, 4-bit codes
sharing the sketch bit) and search uses Table-1 row 1 (k1=1420, k2=370,
h=2, k=30).

Phases (one line each, every check printed with its numbers):

  (a) build    ``HilbertIndex.build`` on the device; resident bytes.
  (b) search   1,000 queries on the Pallas route and on the XLA route:
               recall@30 >= 0.7 against exact search on the host, the two
               routes within a bound derived from f32 accumulation, and
               ``tpu_custom_call`` in the lowered Pallas search chunk.
  (c) serving  ``RetrievalEngine`` over ``MutableHilbertIndex.from_index``:
               4,096 inserts, 1,024 deletes and 256 requests of 1-64
               queries in four interleaved rounds, micro-batches of at
               most 256 rows, then one forced maintenance cycle and epoch
               swap.
  (d) task 2   ``index.knn_graph(configs/gooaq.TABLE2[0])``; recall@15 >=
               0.8 on 1,000 sampled rows against exact search.

``--chips 4`` runs only the sharded phase: ``ShardedHilbertIndex.build``
over four chips on 4 x ``--n`` rows, the "tree" and "gather" merges
bit-equal, recall@30 >= 0.7, and every chip's peak memory within 1.5x of
the least.

Timed phases print ``cold_s`` (first run, compiles included) and, where a
second run fits the time limit, ``warm_s``: both search routes, the
post-swap search, and one Hilbert sort (161 of which make up the build).
The compile cache is the repository's fixed one
(``repro.launch.compile_cache``).  The script refuses to run on
anything but a TPU, exits non-zero if any check fails, and prints as its
last line ``{"ok": true, "device": {...}}`` only when all of them hold.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded phase on four chips
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
D = 384
RECALL30_FLOOR = 0.7   # SISAP 2025 Task 1 bar (configs/pubmed23.py)
RECALL15_FLOOR = 0.8   # SISAP 2025 Task 2 bar (configs/gooaq.py)
U_F32 = 2.0 ** -24     # float32 unit roundoff
# Rows per serving micro-batch (phase c).
MAX_BATCH = 256


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--n", type=int, default=1 << 20,
                    help="corpus rows per chip (default 2^20)")
    ap.add_argument("--queries", type=int, default=1000)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


class Checks:
    """Collects check results; any failure makes the run fail."""

    def __init__(self):
        self.failed = []

    def __call__(self, ok: bool, what: str) -> bool:
        print(f"  check {'ok  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failed.append(what)
        return ok


def _line(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def _timed(fn):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _gamma(n: int) -> float:
    return n * U_F32 / (1 - n * U_F32)


def _route_tolerance(queries, centroids):
    """Per-query bound on |Pallas - XLA| stage-2 distance.

    The kernel computes ||q||^2 - 2 q.r + ||r||^2, the XLA route sum (q-r)^2,
    both in f32 over d terms in some order.  Each sum of d products is off
    by at most gamma_{d+3} times the sum of its terms' magnitudes, and both
    magnitudes are at most (||q|| + ||r||)^2, with ||r|| at most
    R = sqrt(sum_j max_l c_jl^2) for any reconstruction r.  Both routes rank
    the same candidates (stage 1 is integer), so their sorted top-k
    distances differ by at most the same bound.
    """
    import numpy as np

    q = np.asarray(queries, np.float64)
    r_max = np.sqrt((np.asarray(centroids, np.float64) ** 2).max(1).sum())
    qn = np.sqrt((q * q).sum(1))
    return 2.0 * _gamma(q.shape[1] + 3) * (qn + r_max) ** 2


def _exact(data, queries, k):
    from repro.data import ann_datasets

    ids, _ = ann_datasets.exact_knn(data, queries, k, chunk=128)
    return ids


def _peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def phase_build(args, check, data):
    import jax.numpy as jnp

    from repro.configs import pubmed23
    from repro.core import hilbert
    from repro.index import HilbertIndex, IndexConfig
    from repro.index.facade import build_with_timings

    cfg = IndexConfig(forest=pubmed23.FOREST, quantizer=pubmed23.QUANT)
    fcfg = cfg.forest
    pts = jnp.asarray(data)

    # One tree's Hilbert sort first: if the forest would not fit the run,
    # say so now instead of running into the time limit.
    lo, hi = jnp.min(pts, axis=0), jnp.max(pts, axis=0)
    perm = jnp.arange(pts.shape[1], dtype=jnp.int32)
    flip = jnp.zeros(pts.shape[1], bool)

    def one_sort():
        return hilbert.hilbert_sort(pts, bits=fcfg.bits,
                                    key_bits=fcfg.key_bits, lo=lo, hi=hi,
                                    perm=perm, flip=flip)

    _, probe_cold = _timed(one_sort)
    _, probe_warm = _timed(one_sort)
    projected = probe_warm * (fcfg.n_trees + 1)
    _line("a build/probe", one_sort_cold_s=f"{probe_cold:.3f}",
          one_sort_warm_s=f"{probe_warm:.3f}",
          projected_sorts_s=f"{projected:.1f}")
    if not check(projected < 300, "161 Hilbert sorts projected under 300 s"):
        raise RuntimeError("build too slow for the run; phases skipped")

    # The whole build once (compiles included); its warm cost is the
    # probe's warm sort times the 161 sorts it runs, which dominate it.
    (index, split), cold = _timed(lambda: build_with_timings(pts, cfg))
    rep = index.memory_report()
    _line("a build", n=index.n_points, d=index.dim,
          trees=index.forest.n_trees, cold_s=f"{cold:.3f}",
          cold_split_s={k: round(v, 3) for k, v in split.items()},
          resident_bytes=rep["resident_bytes"],
          points_bytes=rep["points_bytes"], forest_bytes=rep["forest_bytes"],
          codes_bytes=rep["codes_bytes"], sketch_bytes=rep["sketch_bytes"])
    check(isinstance(index, HilbertIndex) and index.n_points == args.n
          and index.forest.n_trees == 160,
          "index holds every row and 160 trees")
    return index


def phase_search(args, check, index, queries, truth):
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import pubmed23
    from repro.core import search as search_lib
    from repro.data import ann_datasets

    row1 = pubmed23.TABLE1[0]
    gt = truth.result()
    q = jnp.asarray(queries)
    out = {}
    for backend in ("pallas", "xla"):
        def run():
            return index.search(q, row1, backend=backend)

        res, cold = _timed(run)
        res, warm = _timed(run)
        ids, d2 = (np.asarray(a) for a in res)
        rec = ann_datasets.recall_at_k(ids, gt)
        out[backend] = (ids, d2)
        _line(f"b search/{backend}", queries=len(queries), k1=row1.k1,
              k2=row1.k2, h=row1.h, k=row1.k, cold_s=f"{cold:.3f}",
              warm_s=f"{warm:.3f}", qps_warm=f"{len(queries) / warm:.1f}",
              recall_at_30=f"{rec:.4f}")
        check(rec >= RECALL30_FLOOR,
              f"{backend} recall@30 {rec:.4f} >= {RECALL30_FLOOR}")
        check(bool(np.all(np.isfinite(d2))) and ids.shape == (len(q), 30),
              f"{backend} returns finite (Q, 30) results")

    tol = _route_tolerance(queries, index.quant.centroids)
    dp = np.sort(out["pallas"][1], axis=1)
    dx = np.sort(out["xla"][1], axis=1)
    diff = np.abs(dp - dx).max(axis=1)
    _line("b search/agree", max_abs_diff=f"{diff.max():.3e}",
          tol_min=f"{tol.min():.3e}", tol_max=f"{tol.max():.3e}",
          ids_equal_frac=f"{np.mean(out['pallas'][0] == out['xla'][0]):.4f}")
    check(bool(np.all(diff <= tol)),
          "Pallas and XLA sorted distances agree within the f32 bound")

    # The Pallas route's search chunk, lowered as the facade dispatches it:
    # a Mosaic kernel shows as tpu_custom_call; an interpreter would not.
    f, fcfg = index.forest, index.config.forest
    bucket = min(1 << max(0, (len(queries) - 1).bit_length()),
                 index.config.query_chunk)
    text = search_lib.fused_search_chunk.lower(
        q[:bucket], f.orders, f.directories, f.lo, f.hi, f.perms, f.flips,
        index.master_rank, index.sketches_master, index.codes_master,
        index.master_order, index.quant,
        bits=fcfg.bits, key_bits=fcfg.key_bits, leaf_size=fcfg.leaf_size,
        k1=row1.k1, k2=row1.k2, h=row1.h, k=row1.k, use_kernels=True,
    ).as_text()
    n_calls = text.count("tpu_custom_call")
    _line("b search/kernels", tpu_custom_calls=n_calls)
    check(n_calls >= 2, "tpu_custom_call in the lowered Pallas search chunk")


def phase_serving(args, check, index, extra):
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import pubmed23
    from repro.core import search as search_lib
    from repro.index import MutableHilbertIndex
    from repro.obs.dispatch import accounting_delta, accounting_snapshot
    from repro.serve.engine import RetrievalEngine

    row1 = pubmed23.TABLE1[0]
    rng = np.random.default_rng(args.seed + 1)
    queries, inserts = extra[: args.queries], extra[args.queries:]
    assert len(inserts) == 4096
    deletes = rng.choice(index.n_points, 1024, replace=False)
    sizes = rng.integers(1, 65, 256)
    picks = [rng.integers(0, len(queries), m) for m in sizes]

    # Step mode, four rounds: a quarter of the writes, then a quarter of
    # the requests.  Micro-batches hold at most MAX_BATCH rows; requests
    # that do not fill one wait for the next round's arrivals, and the
    # queue is drained after the last round.
    mut = MutableHilbertIndex.from_index(index, buffer_capacity=8192)
    engine = RetrievalEngine(mut, row1, maintenance=None,
                             max_batch=MAX_BATCH, compaction="thread",
                             start=False)
    try:
        acct0 = accounting_snapshot()
        t0 = time.perf_counter()
        tickets = []
        for r, (ins, dels, reqs) in enumerate(zip(
                np.array_split(inserts, 4), np.array_split(deletes, 4),
                np.array_split(np.arange(len(picks)), 4))):
            engine.insert(ins)
            engine.delete(dels)
            tickets += [(engine.submit(queries[picks[i]]), picks[i])
                        for i in reqs]
            while sum(len(p) for t, p in tickets if not t.done) >= MAX_BATCH:
                engine.step()
        while engine.step():
            pass
        results, failed = [], 0
        for t, p in tickets:
            try:
                ids, d2 = t.result(timeout=0)
            except Exception:  # noqa: BLE001 — counted, then checked
                traceback.print_exc()
                failed += 1
                continue
            results.append(ids.shape == (len(p), row1.k)
                           and bool(np.all(np.isfinite(d2))))
        cold = time.perf_counter() - t0
        serve_compiles = accounting_delta(
            acct0, accounting_snapshot())["recompiles_by_site"]
        check(failed == 0 and len(results) == 256 and all(results),
              f"every ticket returned finite (m, 30) results "
              f"({failed} failed)")
        pre = engine.index
        check(pre.n_live == index.n_points + 4096 - 1024,
              f"live rows {pre.n_live} = N + 4096 - 1024")

        # The write buffer's exact search against host float64 exact search.
        qb = jnp.asarray(queries[:64])
        n_buf = pre._buf_count
        valid = np.zeros((pre.buffer_capacity,), bool)
        valid[:n_buf] = pre._alive[pre._buf_ids[:n_buf]]
        idx, bd2 = search_lib.brute_force_topk(
            qb, jnp.asarray(pre._buf_points), jnp.asarray(valid), k=row1.k)
        live = np.flatnonzero(valid)
        pts = pre._buf_points[live].astype(np.float64)
        qh = np.asarray(qb, np.float64)
        ex = ((qh[:, None, :] - pts[None]) ** 2).sum(-1)
        ex_sorted = np.sort(ex, axis=1)[:, :row1.k]
        tol = 2.0 * _gamma(D + 3) * (
            np.sqrt((qh * qh).sum(1)) + np.sqrt((pts * pts).sum(1)).max()
        ) ** 2
        bdiff = np.abs(np.asarray(bd2, np.float64) - ex_sorted).max(1)
        got = np.asarray(idx)
        rank_ok = all(
            set(live[np.argsort(ex[r])[:row1.k]]) == set(got[r])
            or bdiff[r] <= tol[r]
            for r in range(len(qh)))
        _line("c serving/buffer", buffered=n_buf, live_in_buffer=len(live),
              max_abs_diff=f"{bdiff.max():.3e}", tol_max=f"{tol.max():.3e}")
        check(bool(np.all(bdiff <= tol)) and rank_ok,
              "write-buffer exact search equals host exact search")

        acct1 = accounting_snapshot()
        swapped, maint_s = _timed(lambda: engine.maintain_once(force=True))
        maint_compiles = accounting_delta(
            acct1, accounting_snapshot())["recompiles_by_site"]
        fails = engine.metrics.counter("maintenance_failures")
        touts = engine.metrics.counter("maintenance_timeouts")
        check(swapped and engine.epoch == 1, "one forced maintenance swap")
        check(fails == 0 and touts == 0,
              f"maintenance_failures={fails} maintenance_timeouts={touts}")

        # A full batch, the shape maintenance pre-warmed the new epoch with.
        qs = np.concatenate([queries[p] for p in picks])[:MAX_BATCH]
        (e_ids, e_d), warm = _timed(lambda: engine.search(qs))
        i_ids, i_d = engine.index.search(jnp.asarray(qs), row1)
        same = (np.array_equal(np.asarray(e_ids), np.asarray(i_ids))
                and np.array_equal(np.asarray(e_d), np.asarray(i_d)))
        check(same, "post-swap engine search == direct search on new epoch")
        _line("c serving", requests=256, rows=int(sizes.sum()),
              max_batch=MAX_BATCH, batches=engine.metrics.counter("batches"),
              inserts=4096, deletes=1024, cold_s=f"{cold:.3f}",
              compiles=serve_compiles, warm_s=f"{warm:.3f}",
              warm_rows=len(qs), maintain_s=f"{maint_s:.3f}",
              maintain_compiles=maint_compiles, epoch=engine.epoch,
              failed_tickets=failed, maintenance_failures=fails,
              maintenance_timeouts=touts, segments=engine.index.n_segments)
    finally:
        engine.stop()


def phase_graph(args, check, index, rows, truth):
    import numpy as np

    from repro.configs import gooaq
    from repro.data import ann_datasets

    # One pass: it is 80 Hilbert sorts plus 80 merges of 2^20 x 156
    # candidates; a second, warm pass would not fit the run.
    params = gooaq.TABLE2[0]
    res, cold = _timed(lambda: index.knn_graph(params))
    ids = np.asarray(res[0])
    ex = truth.result()
    exact = np.stack([r[r != i][: params.k] for r, i in zip(ex, rows)])
    rec = ann_datasets.recall_at_k(ids[rows], exact)
    _line("d knn_graph", n=index.n_points, n_orders=params.n_orders,
          k1=params.k1, k2=params.k2, k=params.k, cold_s=f"{cold:.3f}",
          recall_at_15=f"{rec:.4f}")
    check(rec >= RECALL15_FLOOR,
          f"knn_graph recall@15 {rec:.4f} >= {RECALL15_FLOOR}")


def phase_sharded(args, check, data, queries, truth):
    import jax
    import numpy as np

    from repro.configs import pubmed23
    from repro.data import ann_datasets
    from repro.index import IndexConfig, ShardedHilbertIndex
    from repro.launch.mesh import data_mesh

    row1 = pubmed23.TABLE1[0]
    mesh = data_mesh(4)
    cfg = IndexConfig(forest=pubmed23.FOREST, quantizer=pubmed23.QUANT)
    index, cold = _timed(lambda: ShardedHilbertIndex.build(data, cfg,
                                                            mesh=mesh))
    rep = index.memory_report()
    _line("s build", n=index.n_points, shards=index.n_shards,
          cold_s=f"{cold:.3f}", per_device_bytes=rep["per_device_bytes"])
    gt = truth.result()
    out = {}
    for merge in ("tree", "gather"):
        res, c = _timed(lambda: index.search(queries, row1, merge=merge))
        res, w = _timed(lambda: index.search(queries, row1, merge=merge))
        ids, d2 = (np.asarray(a) for a in res)
        rec = ann_datasets.recall_at_k(ids, gt)
        out[merge] = d2
        _line(f"s search/{merge}", queries=len(queries), cold_s=f"{c:.3f}",
              warm_s=f"{w:.3f}", recall_at_30=f"{rec:.4f}")
        check(rec >= RECALL30_FLOOR,
              f"{merge} merge recall@30 {rec:.4f} >= {RECALL30_FLOOR}")
    check(np.array_equal(np.sort(out["tree"], 1), np.sort(out["gather"], 1)),
          "tree and gather merges: sorted distances bit-equal")
    peaks = [_peak_bytes(d) for d in mesh.devices.flat]
    _line("s memory", peak_bytes_in_use=peaks)
    check(min(peaks) > 0 and max(peaks) <= 1.5 * min(peaks),
          "every chip's peak within 1.5x of the least")


def run(args) -> int:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError:
        print("chip_smoke: the repro package is not beside this script; "
              "run it from a checkout of the repository", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    import jax
    import numpy as np

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r}); "
              "this smoke runs only on the chip", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX finds "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    from repro.data import ann_datasets

    _line("device", platform=dev.platform, kind=repr(dev.device_kind),
          count=len(devices), jax=jax.__version__, cache=cache)
    check = Checks()
    t0 = time.perf_counter()
    n = args.n * args.chips
    extra_rows = 4096 if args.chips == 1 else 0
    data, extra = ann_datasets.lowrank_dataset_with_queries(
        n, args.queries + extra_rows, D, seed=args.seed)
    queries = extra[: args.queries]
    _line("data", n=n, d=D, queries=args.queries, seed=args.seed,
          gen_s=f"{time.perf_counter() - t0:.3f}")

    def phase(name, fn, *a):
        try:
            return fn(args, check, *a)
        except Exception:  # noqa: BLE001 — recorded as a failed check
            traceback.print_exc()
            check(False, f"{name} raised")
            return None

    # Exact answers are computed on the host while the chip builds.
    if args.chips == 4:
        with ThreadPoolExecutor(max_workers=1) as pool:
            truth_q = pool.submit(_exact, data, queries, 30)
            phase("sharded", phase_sharded, data, queries, truth_q)
    else:
        rows = np.random.default_rng(args.seed + 2).choice(
            n, 1000, replace=False)
        with ThreadPoolExecutor(max_workers=1) as pool:
            truth_q = pool.submit(_exact, data, queries, 30)
            truth_g = pool.submit(_exact, data, data[rows], 16)
            index = phase("build", phase_build, data)
            if index is not None:
                phase("search", phase_search, index, queries, truth_q)
                phase("serving", phase_serving, index, extra)
                phase("knn_graph", phase_graph, index, rows, truth_g)
        _line("memory", peak_bytes_in_use=_peak_bytes(dev))
    _line("total", seconds=f"{time.perf_counter() - t0:.3f}",
          failed=len(check.failed))
    if check.failed:
        print("chip_smoke: FAILED: " + "; ".join(check.failed),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(run(_parse(sys.argv[1:])))
