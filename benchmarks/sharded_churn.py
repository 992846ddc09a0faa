"""Sharded churn workload: streaming writes on the row-partitioned index.

The sharded analogue of ``benchmarks.churn`` — quantifies what a
multi-device streaming deployment cares about:

* **recall-vs-rebuild** — after each churn phase, recall@k of the live
  sharded LSM state against exact brute force and against a from-scratch
  ``ShardedHilbertIndex`` build over the same live points, plus the
  rebuild's wall-clock cost the mutable layout avoids paying;
* **one-dispatch invariant** — every streaming search runs in exactly ONE
  jitted dispatch per query chunk regardless of generation count
  (asserted, not assumed);
* **routing locality** — the fraction of streamed inserts whose
  curve-range routing agrees with where a full re-partition would place
  them (how well the frozen bounds track the data);
* **compaction endpoint** — post-compact latency/recall, where search is
  bit-equal to the fresh rebuild (asserted).

Results land in ``BENCH_sharded_churn.json`` (cwd).  ``--smoke`` shrinks
to CI scale; also runnable via ``python -m benchmarks.run sharded_churn``.
Like ``benchmarks.sharded_search``, the measurement re-execs itself in a
subprocess with ``--xla_force_host_platform_device_count=8``.
"""

import json
import os
import subprocess
import sys

_WORKER_ENV = "_SHARDED_CHURN_BENCH_WORKER"


def main(smoke: bool = False) -> dict:
    # Re-exec only on the CPU, for virtual devices.  On an accelerator this
    # process holds the chip, so the worker runs here.
    import jax

    if os.environ.get(_WORKER_ENV) != "1" and jax.default_backend() == "cpu":
        env = dict(os.environ)
        env[_WORKER_ENV] = "1"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (os.path.join(root, "src"),
                        env.get("PYTHONPATH", "")) if p
        )
        cmd = [sys.executable, "-m", "benchmarks.sharded_churn"]
        if smoke:
            cmd.append("--smoke")
        r = subprocess.run(cmd, env=env, cwd=os.getcwd())
        if r.returncode != 0:
            raise SystemExit(f"sharded churn bench worker failed ({r.returncode})")
        with open("BENCH_sharded_churn.json") as f:
            return json.load(f)
    return _worker(smoke)


def _worker(smoke: bool) -> dict:
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import distributed
    from repro.data import ann_datasets
    from repro.index import (
        ForestConfig,
        IndexConfig,
        SearchParams,
        ShardedHilbertIndex,
        ShardedMutableHilbertIndex,
    )
    from repro.launch.mesh import data_mesh
    from repro.obs import accounting_snapshot

    n_shards = min(8, jax.device_count())
    if smoke:
        n0, d, q, batches, batch, reps = 2048, 24, 32, 2, 256, 3
        fcfg = ForestConfig(n_trees=2, bits=4, key_bits=96, leaf_size=16)
        params = SearchParams(k1=16, k2=64, h=1, k=10)
        capacity, max_segments = 128, 4
    else:
        n0, d, q, batches, batch, reps = 32768, 96, 256, 5, 4096, 15
        fcfg = ForestConfig(n_trees=8, bits=4, key_bits=384, leaf_size=32)
        params = SearchParams(k1=32, k2=192, h=2, k=10)
        capacity, max_segments = 1024, 8
    cfg = IndexConfig(forest=fcfg)
    mesh = data_mesh(n_shards)
    total = n0 + batches * batch
    data, queries = ann_datasets.lowrank_dataset_with_queries(
        total, q, d, n_clusters=32, seed=0
    )
    data = np.asarray(data)
    queries_j = jnp.asarray(queries)
    rng = np.random.default_rng(0)

    def timed(search):
        search()  # warm the jit caches for this LSM shape
        out = []
        for _ in range(reps):
            t0 = time.perf_counter()
            ids, _ = search()
            jnp.asarray(ids).block_until_ready()
            out.append(1000 * (time.perf_counter() - t0))
        s = np.sort(np.asarray(out))
        return (float(s[int(0.50 * (len(s) - 1))]),
                float(s[int(0.99 * (len(s) - 1))]))

    mut = ShardedMutableHilbertIndex.build(
        jnp.asarray(data[:n0]), cfg, mesh=mesh,
        buffer_capacity=capacity, max_segments=max_segments,
    )
    live_ids = np.arange(n0, dtype=np.int64)
    live_pts = data[:n0]
    route_agree = []

    rows = []
    print("phase,n_live,n_segments,n_buffered,recall_mut,recall_rebuild,"
          "rebuild_s,p50_ms,p99_ms,dispatches")
    for phase in range(batches + 1):
        p50, p99 = timed(lambda: mut.search(queries_j, params))
        mut.search(queries_j, params)
        dispatches = mut.last_dispatch_count
        assert dispatches == -(-q // cfg.query_chunk), dispatches

        gt, _ = ann_datasets.exact_knn(live_pts, np.asarray(queries), params.k)
        hits, _ = mut.search(queries_j, params)
        pos_of = {int(e): i for i, e in enumerate(live_ids)}
        pos = np.vectorize(lambda e: pos_of.get(int(e), -1))(np.asarray(hits))
        rec = ann_datasets.recall_at_k(pos, gt)
        t0 = time.time()
        fresh = ShardedHilbertIndex.build(jnp.asarray(live_pts), cfg, mesh=mesh)
        rebuild_s = time.time() - t0
        frec = ann_datasets.recall_at_k(
            np.asarray(fresh.search(queries_j, params)[0]), gt
        )
        row = {
            "phase": phase, "n_live": mut.n_live,
            "n_segments": mut.n_segments, "n_buffered": mut.n_buffered,
            "recall_mut": float(rec), "recall_rebuild": float(frec),
            "rebuild_s": float(rebuild_s), "p50_ms": p50, "p99_ms": p99,
            "dispatches_per_chunk": int(dispatches),
        }
        rows.append(row)
        print(f"{phase},{mut.n_live},{mut.n_segments},{mut.n_buffered},"
              f"{rec:.3f},{frec:.3f},{rebuild_s:.2f},{p50:.1f},{p99:.1f},"
              f"{dispatches}", flush=True)

        if phase == batches:
            break
        # churn: insert a batch (measuring routing locality), expire ~8%.
        # Locality = how often the FROZEN partition bounds send a new row
        # to the same shard a full re-partition of live+batch would.
        s = n0 + phase * batch
        batch_pts = data[s : s + batch]
        if mut._bounds is not None:
            routed = mut._route(batch_pts)
            union = np.concatenate([live_pts, batch_pts])
            parts = distributed.hilbert_partition(
                jnp.asarray(union), fcfg, mesh=mesh, n_shards=n_shards
            )
            owner = np.zeros((len(union),), np.int32)
            for si, g in enumerate(parts):
                owner[np.asarray(g)] = si
            route_agree.append(float(np.mean(
                routed == owner[len(live_pts):]
            )))
        new = mut.insert(batch_pts)
        drop = rng.choice(live_ids, len(live_ids) // 12, replace=False)
        mut.delete(drop)
        keep = ~np.isin(live_ids, drop)
        live_ids = np.concatenate([live_ids[keep], new])
        live_pts = np.concatenate([live_pts[keep], batch_pts])

    # compacted endpoint: bit-equal to the fresh rebuild
    t0 = time.time()
    mut.compact()
    compact_s = time.time() - t0
    p50c, p99c = timed(lambda: mut.search(queries_j, params))
    order = np.argsort(live_ids, kind="stable")
    live_ids_s, live_pts_s = live_ids[order], live_pts[order]
    fresh = ShardedHilbertIndex.build(jnp.asarray(live_pts_s), cfg, mesh=mesh)
    fi, fd = fresh.search(queries_j, params)
    mi, md = mut.search(queries_j, params)
    exp = np.where(np.asarray(fi) >= 0,
                   live_ids_s[np.clip(np.asarray(fi), 0, None)], -1)
    bit_equal = bool(
        np.array_equal(exp, np.asarray(mi))
        and np.array_equal(np.asarray(fd), np.asarray(md))
    )
    assert bit_equal, "post-compact search must equal the fresh rebuild"
    print(f"compacted,{mut.n_live},{mut.n_segments},0,bit_equal={bit_equal},"
          f",{compact_s:.2f},{p50c:.1f},{p99c:.1f},1", flush=True)

    rep = mut.memory_report()
    result = {
        "n0": n0, "d": d, "q": q, "batch": batch, "batches": batches,
        "n_shards": n_shards, "buffer_capacity": capacity,
        "max_segments": max_segments,
        "params": {"k1": params.k1, "k2": params.k2, "h": params.h,
                   "k": params.k},
        "phases": rows,
        "routing_agreement_mean": (
            float(np.mean(route_agree)) if route_agree else None
        ),
        "compacted": {
            "compact_s": float(compact_s), "p50_ms": p50c, "p99_ms": p99c,
            "bit_equal_to_fresh_rebuild": bit_equal,
        },
        "memory": {
            "sharded_bytes": rep["sharded_bytes"],
            "replicated_bytes": rep["replicated_bytes"],
            "per_device_bytes": rep["per_device_bytes"][0],
            "buffer_bytes": rep["buffer_bytes"],
        },
        "dispatch_accounting": accounting_snapshot(),
    }
    with open("BENCH_sharded_churn.json", "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result, indent=2))
    print("\nwrote BENCH_sharded_churn.json", flush=True)
    return result


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv[1:])
