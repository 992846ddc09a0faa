"""Benchmark orchestrator: one module per paper table/figure.

All table/phase benchmarks run on the unified ``repro.index.HilbertIndex``
API (build once → search / knn_graph off the same artifact).

  table1   — Task-1 recall/time grid (paper Table 1)
  table2   — Task-2 graph build time/recall (paper Table 2)
  phases   — preprocessing time split (paper §3.2)
  kernels  — hamming/qdist microbench + TPU roofline model
  hsort    — Hilbert-sort scaling (2016 algorithm claim)
  churn    — streaming insert/delete/search on the mutable index
  search   — fused packed search path vs per-tree-loop reference
             (emits BENCH_search.json)
  sharded  — row-partitioned shard_map search vs single-device
             (emits BENCH_sharded.json; re-execs itself with 8
             simulated devices)
  sharded_churn — streaming insert/delete/compact on the sharded-mutable
             index: recall-vs-rebuild, one-dispatch invariant, routing
             locality (emits BENCH_sharded_churn.json; re-execs itself
             with 8 simulated devices)
  serving  — concurrent write+query load through the RetrievalEngine:
             per-request p50/p99/p999 with and without background
             maintenance (emits BENCH_serving.json; re-execs itself
             with 8 simulated devices)
  durability — WAL ack-latency overhead vs sync_every and recovery
             time vs replay-tail length; asserts the default group
             commit stays <10% p50 on sustained ingest (emits
             BENCH_durability.json)

``python -m benchmarks.run [names...]`` (default: all).
"""

import sys
import time


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    names = sys.argv[1:] or ["kernels", "hsort", "phases", "table2", "table1",
                             "churn", "search", "sharded", "sharded_churn",
                             "serving", "durability"]
    t00 = time.time()
    for name in names:
        print(f"\n===== {name} =====", flush=True)
        t0 = time.time()
        if name == "table1":
            from benchmarks import task1_table1 as m
        elif name == "table2":
            from benchmarks import task2_table2 as m
        elif name == "phases":
            from benchmarks import build_phases as m
        elif name == "kernels":
            from benchmarks import kernel_bench as m
        elif name == "hsort":
            from benchmarks import hilbert_sort_bench as m
        elif name == "churn":
            from benchmarks import churn as m
        elif name == "search":
            from benchmarks import search_path as m
        elif name == "sharded":
            from benchmarks import sharded_search as m
        elif name == "sharded_churn":
            from benchmarks import sharded_churn as m
        elif name == "serving":
            from benchmarks import serving as m
        elif name == "durability":
            from benchmarks import durability as m
        else:
            raise SystemExit(f"unknown benchmark {name!r}")
        m.main()
        print(f"[{name} done in {time.time()-t0:.0f}s]", flush=True)
    print(f"\nALL BENCHMARKS DONE in {time.time()-t00:.0f}s")


if __name__ == "__main__":
    main()
