"""Concurrent write+query serving load: the engine's reason to exist.

``BENCH_sharded_churn.json`` showed WHY serving needs an engine: query
p50 degrades ~8x as sealed generations pile up, and a synchronous
``compact()`` stalls the caller for seconds.  This benchmark measures the
fix — the same concurrent write+query load is driven through
:class:`repro.serve.RetrievalEngine` in three phases on the
sharded-mutable layout:

* **baseline** — query stream only, no writes: the latency floor;
* **churn** — a background writer streams inserts/deletes while queries
  run, background maintenance OFF: generations accumulate and tail
  latency creeps (what the seed's serving path would experience);
* **churn_maintained** — same write load with the maintenance thread ON:
  tier compaction runs on a shadow copy off the query path and the
  serving index is atomically swapped, so the generation count stays
  bounded while NO query ever waits on a compaction;
* **churn_maintained_subprocess** — the reader-concurrency A/B against
  the previous phase: identical load, but the shadow compacts in a CHILD
  process (``compaction="subprocess"``) and two serve workers execute
  batches concurrently under the shared read side of the engine's
  reader-writer lock.  The in-thread phase is the PR-6 architecture's
  number; this phase is the rw-lock + out-of-process one.  Each
  maintained phase's swap timeline also records per-phase
  ``*_locked`` booleans, from which the artifact asserts the serve lock
  was held exclusively ONLY during snapshot and swap — never during the
  compact or the catch-up replay;
* **baseline_obs** — the baseline load with span tracing toggled per
  request (interleaved A/B within one phase): the traced-vs-untraced
  p50 delta is the tracing/metrics tax, clean of cross-phase drift;
* **baseline_probe** — tracing ON plus a 25% online recall probe: its
  rolling recall is checked against an offline exact evaluation, and its
  p50 delta prices the probe's shadow scorer (which on this CPU harness
  contends with serving for cores).  All of it lands in the artifact's
  ``observability`` block.  Dispatch/recompile accounting is on in every
  phase; each phase reports its post-warmup per-site deltas.

Two latency series are reported per phase:

* **request** — submit -> result wall time (queue + serve-lock wait
  included): what a caller experiences end to end;
* **search** — the search execution itself (the engine's
  ``batch_latency``, timed inside the serve lock): the query path
  proper, which is what the swap protocol keeps off the compaction.

plus the maintained/baseline p99 ratios for both.  The acceptance
target is maintained p99 within 2x of the no-write baseline.  CAVEAT
for this CPU harness: the "device" here IS the host cores, so the
shadow compaction unavoidably contends with serving for the same
silicon and inflates both series while it runs — on a real accelerator
the compact's build executes beside the serving device, which is the
deployment the 2x target describes.  The artifact records both ratios
honestly; track the trend, not the absolute, on CPU.

Results land in ``BENCH_serving.json`` (cwd).  ``--smoke`` shrinks to
CI scale AND drops to the single-device ``MutableHilbertIndex`` layout:
the engine is layout-agnostic (the sharded engine paths are exercised
by ``tests/test_engine.py`` in the same CI job), and sustained
write+compile load over 8 *virtual* CPU devices starves XLA's
collective rendezvous for minutes at a time — a harness artifact, not
a serving property.  The full run uses the 8-shard sharded-mutable
layout and re-execs itself in a subprocess with
``--xla_force_host_platform_device_count=8``.  Also runnable via
``python -m benchmarks.run serving``.
"""

import json
import os
import subprocess
import sys

_WORKER_ENV = "_SERVING_BENCH_WORKER"


def main(smoke: bool = False) -> dict:
    # Re-exec only on the CPU, for virtual devices.  On an accelerator this
    # process holds the chip, so the worker runs here.
    import jax

    if os.environ.get(_WORKER_ENV) != "1" and jax.default_backend() == "cpu":
        env = dict(os.environ)
        env[_WORKER_ENV] = "1"
        if not smoke:  # smoke runs the single-device mutable layout
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8"
            ).strip()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (os.path.join(root, "src"),
                        env.get("PYTHONPATH", "")) if p
        )
        cmd = [sys.executable, "-m", "benchmarks.serving"]
        if smoke:
            cmd.append("--smoke")
        r = subprocess.run(cmd, env=env, cwd=os.getcwd())
        if r.returncode != 0:
            raise SystemExit(f"serving bench worker failed ({r.returncode})")
        with open("BENCH_serving.json") as f:
            return json.load(f)
    return _worker(smoke)


def _worker(smoke: bool) -> dict:
    import threading
    import time

    import jax
    import numpy as np

    from repro.data import ann_datasets
    from repro.index import (
        ForestConfig,
        IndexConfig,
        MutableHilbertIndex,
        SearchParams,
        ShardedMutableHilbertIndex,
    )
    from repro import obs
    from repro.launch.mesh import data_mesh
    from repro.obs import (
        RecallProbeConfig,
        accounting_snapshot,
        dispatch_counts,
        exact_topk,
        live_points,
        recall_at_k,
        recompile_counts,
    )
    from repro.serve import MaintenancePolicy, RetrievalEngine
    from repro.serve.metrics import LatencyRecorder, percentiles

    n_shards = 1 if smoke else min(8, jax.device_count())
    if smoke:
        n0, d, requests, q_batch = 4096, 24, 150, 32
        fcfg = ForestConfig(n_trees=2, bits=4, key_bits=96, leaf_size=16)
        params = SearchParams(k1=16, k2=64, h=1, k=10)
        capacity, write_batch, warm_swaps, warm_cap_s = 256, 64, 2, 240.0
    else:
        n0, d, requests, q_batch = 32768, 96, 300, 256
        fcfg = ForestConfig(n_trees=8, bits=4, key_bits=384, leaf_size=32)
        params = SearchParams(k1=32, k2=192, h=2, k=10)
        capacity, write_batch, warm_swaps, warm_cap_s = 1024, 512, 2, 600.0
    # writer pacing: one batch per ~write_pause — heavy but bounded churn
    # (an unthrottled writer saturates the serve lock and measures lock
    # starvation, not serving)
    write_pause = 0.05
    cfg = IndexConfig(forest=fcfg)
    mesh = None if n_shards == 1 else data_mesh(n_shards)
    # spare rows for the churn writers (they wrap within this region)
    total = n0 + 64 * write_batch
    data, queries = ann_datasets.lowrank_dataset_with_queries(
        total, q_batch, d, n_clusters=32, seed=0
    )
    data, queries = np.asarray(data), np.asarray(queries)
    policy = MaintenancePolicy(
        max_segments=4, max_tombstone_ratio=0.5, poll_interval_s=0.05
    )

    def run_phase(name, *, churn, maintained, obs_on=False,
                  obs_ab=False, recall_fraction=None,
                  compaction="thread", serve_threads=1):
        # obs_on: the full observability stack — span tracing, a recall
        # probe sampling served batches — is live for the measured window
        # (the A/B against the identical obs-off phase is the overhead
        # number the artifact reports).  Dispatch/recompile accounting is
        # unconditional (the scopes are always on), so every phase gets
        # post-warmup recompile deltas for free.
        obs.default_tracer().enabled = bool(obs_on)
        if mesh is None:
            index = MutableHilbertIndex(
                cfg, buffer_capacity=capacity, max_segments=16
            )
            index.insert(data[:n0])
            index.compact()  # start from one sealed segment
        else:
            index = ShardedMutableHilbertIndex.build(
                data[:n0], cfg, mesh=mesh,
                buffer_capacity=capacity, max_segments=16,
            )
        eng = RetrievalEngine(
            index, params,
            maintenance=policy if maintained else None,
            recall=(RecallProbeConfig(fraction=recall_fraction, seed=0)
                    if recall_fraction else None),
            compaction=compaction,
            serve_threads=serve_threads,
            start=True,
        )
        stop = threading.Event()
        inserted_ids: list = []

        def writer():
            s = n0
            while not stop.is_set():
                ids = eng.insert(data[s : s + write_batch])
                inserted_ids.append(ids)
                if len(inserted_ids) > 2:
                    old = inserted_ids.pop(0)  # rolling-window expiry
                    eng.delete(old)
                s += write_batch
                if s + write_batch > total:
                    s = n0  # wrap within the spare region
                if stop.wait(write_pause):
                    return

        th = None
        if churn:
            th = threading.Thread(target=writer)
            th.start()
        # Warm-up (unmeasured): a long-running deployment's jit caches
        # hold every recurring LSM shape.  The maintained phase reaches
        # that steady state only after a couple of full maintenance
        # cycles (compact shapes + post-swap buffer buckets), so keep
        # serving unmeasured until `warm_swaps` swaps have landed (time
        # capped); other phases just warm the query-shape dispatch.
        warm_t0 = time.perf_counter()
        warm_requests = 0
        while True:
            eng.search(queries)
            warm_requests += 1
            if not maintained or eng.metrics.counter("swaps") >= warm_swaps:
                break
            if time.perf_counter() - warm_t0 > warm_cap_s:
                break
        # fresh search-exec ring: measure the query path post-warmup only
        eng.metrics.batch_latency = LatencyRecorder()
        warm_swaps_seen = eng.metrics.counter("swaps")
        warm_s = time.perf_counter() - warm_t0
        d_warm, r_warm = dispatch_counts(), recompile_counts()
        lat = []
        lat_ab = {True: [], False: []}  # obs_ab: traced vs untraced
        t0 = time.perf_counter()
        try:
            for r in range(requests):
                if obs_ab:
                    # interleaved A/B: alternate tracing per request so
                    # both series see identical load, cache, and thermal
                    # conditions — phase-to-phase drift on a busy CPU
                    # host dwarfs the tracing tax, an interleave doesn't
                    obs.default_tracer().enabled = (r % 2 == 0)
                ticket = eng.submit(queries)
                ticket.result(timeout=600)
                lat.append(ticket.latency_ms)
                if obs_ab:
                    lat_ab[r % 2 == 0].append(ticket.latency_ms)
        finally:
            if th is not None:
                stop.set()
                th.join()
            eng.stop(drain=True)
        wall_s = time.perf_counter() - t0
        stats = eng.maintenance_stats()
        search_ms = eng.metrics.batch_latency.samples()
        # per-site dispatch/recompile deltas over the measured window:
        # the steady-state invariant says the *search* sites stay at 0
        # recompiles after warmup (seal/compact sites may legitimately
        # compile fresh generation shapes under churn)
        d_end, r_end = dispatch_counts(), recompile_counts()
        dispatches_meas = {
            s: d_end[s] - d_warm.get(s, 0)
            for s in d_end if d_end[s] - d_warm.get(s, 0)
        }
        recompiles_meas = {
            s: r_end[s] - r_warm.get(s, 0)
            for s in r_end if r_end[s] - r_warm.get(s, 0)
        }
        online_recall = offline_recall = None
        if eng.recall_probe is not None:
            # stop(drain=True) above scored the stragglers; compare the
            # rolling online estimate against an offline exact evaluation
            # of the same queries on the final index state
            online_recall = float(eng.recall_probe.recall())
            final = eng.index
            direct_ids, _ = final.search(queries, params)
            truth = live_points(final)
            if truth is not None:
                exact = exact_topk(queries, truth[0], truth[1], params.k)
                offline_recall = float(
                    recall_at_k(np.asarray(direct_ids), exact).mean()
                )
        row = {
            "phase": name,
            "requests": requests,
            "warmup_requests": warm_requests,
            "warmup_s": float(warm_s),
            "rows_per_request": q_batch,
            "wall_s": float(wall_s),
            "qps": float(requests / wall_s),
            **percentiles(lat),
            "max_ms": float(np.max(lat)),
            "search": percentiles(search_ms),
            "swaps_in_window": (
                eng.metrics.counter("swaps") - warm_swaps_seen
            ),
            "swaps": eng.metrics.counter("swaps"),
            "maintenance_runs": eng.metrics.counter("maintenance_runs"),
            "inserts": eng.metrics.counter("inserts"),
            "deletes": eng.metrics.counter("deletes"),
            "end_segments": int(stats.get("n_segments", 0)),
            "end_live": int(stats.get("n_live", 0)),
            "obs_on": bool(obs_on),
            "compaction": compaction,
            "serve_threads": serve_threads,
            "dispatches_measured": dispatches_meas,
            "recompiles_measured": recompiles_meas,
            # rw-lock contention over the whole phase (incl. warmup):
            # how often searches shared the read side, how long writes
            # actually kept them out
            "rwlock": {
                k: float(v) for k, v in eng._serve_lock.stats().items()
                if k in ("read_acquisitions", "write_acquisitions",
                         "read_wait_ms", "write_wait_ms", "write_held_ms")
            },
        }
        if eng.last_swap_timeline is not None:
            tl = eng.last_swap_timeline
            # the lock-exclusivity proof, from recorded maint timings:
            # exclusive at snapshot + swap, shared/free elsewhere
            row["swap_timeline_locks"] = {
                k: tl.get(k) for k in ("snapshot_locked", "compact_locked",
                                       "replay_locked", "swap_locked")
            }
            row["swap_ms"] = tl.get("swap_ms")
            row["snapshot_ms"] = tl.get("snapshot_ms")
            row["compact_ms"] = tl.get("compact_ms")
        if online_recall is not None:
            row["recall_online"] = online_recall
            row["recall_offline"] = offline_recall
        if obs_ab:
            row["p50_obs_on"] = percentiles(lat_ab[True])["p50"]
            row["p50_obs_off"] = percentiles(lat_ab[False])["p50"]
        print(
            f"{name}: p50={row['p50']:.1f}ms p99={row['p99']:.1f}ms "
            f"p999={row['p999']:.1f}ms qps={row['qps']:.1f} "
            f"swaps={row['swaps']} segments={row['end_segments']} "
            f"(inserts={row['inserts']})",
            flush=True,
        )
        return row

    print(f"serving load: {requests} requests x {q_batch} queries, "
          f"{n_shards} shard(s), corpus n0={n0} d={d}", flush=True)
    baseline = run_phase("baseline", churn=False, maintained=False)
    churn = run_phase("churn", churn=True, maintained=False)
    maintained = run_phase("churn_maintained", churn=True, maintained=True)
    # reader-concurrency A/B: identical load, out-of-process compaction
    # + two serve workers sharing the read lock (vs in-thread above)
    maintained_sub = run_phase(
        "churn_maintained_subprocess", churn=True, maintained=True,
        compaction="subprocess", serve_threads=2,
    )
    # A/B for the observability tax: the baseline load with tracing
    # toggled per request (interleaved within ONE phase — see run_phase).
    # The recall probe gets its own phase: its exact shadow scoring runs
    # on a second thread, which on this host==device harness contends
    # with serving for the same cores, so folding it into the overhead
    # A/B would measure core contention, not the tracing/metrics tax (on
    # an accelerator the shadow is pure host work beside the device).
    # Both taxes land in the artifact.
    baseline_obs = run_phase(
        "baseline_obs", churn=False, maintained=False, obs_ab=True,
    )
    baseline_probe = run_phase(
        "baseline_probe", churn=False, maintained=False,
        obs_on=True, recall_fraction=0.25,
    )
    obs.default_tracer().enabled = False

    ratio_churn = churn["p99"] / max(baseline["p99"], 1e-9)
    ratio_maintained = maintained["p99"] / max(baseline["p99"], 1e-9)
    s_ratio_churn = (churn["search"]["p99"]
                     / max(baseline["search"]["p99"], 1e-9))
    s_ratio_maintained = (maintained["search"]["p99"]
                          / max(baseline["search"]["p99"], 1e-9))
    ratio_sub = maintained_sub["p99"] / max(baseline["p99"], 1e-9)
    s_ratio_sub = (maintained_sub["search"]["p99"]
                   / max(baseline["search"]["p99"], 1e-9))
    result = {
        "n0": n0, "d": d, "n_shards": n_shards,
        "layout": "mutable" if mesh is None else "sharded_mutable",
        "requests": requests, "q_batch": q_batch,
        "write_batch": write_batch, "buffer_capacity": capacity,
        "write_pause_s": write_pause,
        "params": {"k1": params.k1, "k2": params.k2, "h": params.h,
                   "k": params.k},
        "policy": {"max_segments": policy.max_segments,
                   "max_tombstone_ratio": policy.max_tombstone_ratio},
        "phases": [baseline, churn, maintained, maintained_sub,
                   baseline_obs, baseline_probe],
        "p99_ratio_churn_vs_baseline": float(ratio_churn),
        "p99_ratio_maintained_vs_baseline": float(ratio_maintained),
        "p99_ratio_maintained_subprocess_vs_baseline": float(ratio_sub),
        "search_p99_ratio_churn_vs_baseline": float(s_ratio_churn),
        "search_p99_ratio_maintained_vs_baseline": float(s_ratio_maintained),
        "search_p99_ratio_maintained_subprocess_vs_baseline": float(
            s_ratio_sub
        ),
        "maintained_within_2x_of_baseline": bool(ratio_maintained <= 2.0),
        "maintained_search_within_2x_of_baseline": bool(
            s_ratio_maintained <= 2.0
        ),
        "cpu_caveat": (
            "host==device on this harness: the shadow compact contends "
            "with serving for the same cores while it runs (see module "
            "docstring); on an accelerator the compact builds beside the "
            "serving device"
        ),
    }
    # Reader-concurrency acceptance block: the in-thread vs
    # out-of-process A/B, and the lock-exclusivity proof read back from
    # the recorded maint timelines (exclusive ONLY at snapshot + swap).
    with_tl = [ph for ph in (maintained, maintained_sub)
               if ph.get("swap_timeline_locks") is not None]
    locks_ok = bool(with_tl) and all(
        ph["swap_timeline_locks"]["snapshot_locked"] is True
        and ph["swap_timeline_locks"]["swap_locked"] is True
        and ph["swap_timeline_locks"]["compact_locked"] is False
        and ph["swap_timeline_locks"]["replay_locked"] is False
        for ph in with_tl
    )
    result["reader_concurrency"] = {
        "in_thread_search_p99_ms": maintained["search"]["p99"],
        "subprocess_search_p99_ms": maintained_sub["search"]["p99"],
        "subprocess_search_p99_improves": bool(
            maintained_sub["search"]["p99"] <= maintained["search"]["p99"]
        ),
        "in_thread_request_p99_ms": maintained["p99"],
        "subprocess_request_p99_ms": maintained_sub["p99"],
        "subprocess_serve_threads": 2,
        "lock_exclusive_only_at_snapshot_and_swap": locks_ok,
        "exclusive_hold_ms_in_thread": maintained["rwlock"][
            "write_held_ms"
        ],
        "exclusive_hold_ms_subprocess": maintained_sub["rwlock"][
            "write_held_ms"
        ],
        # the exclusive window around the swap itself — the number the
        # rw-lock + subprocess protocol shrinks on ANY host (the child
        # compacts outside the lock and outside the process, so the
        # parent's write side covers only the final tail replay + flip)
        "swap_exclusive_ms_in_thread": maintained.get("swap_ms"),
        "swap_exclusive_ms_subprocess": maintained_sub.get("swap_ms"),
        "cpu_caveat": (
            "the p99 A/B needs >=2 host cores to show the isolation "
            "win: with one core the compactor child pays interpreter + "
            "jax startup per cycle AND timeshares the serving core, so "
            "its longer compact window inflates p99 instead of freeing "
            "it.  The structural guarantee holds regardless (asserted "
            "above): the serve lock is exclusive only at snapshot + "
            "swap, and in both modes the exclusive swap window covers "
            "only the final WAL tail + pointer flip — independent of "
            "how long the compact itself ran, because compaction and "
            "catch-up replay happen outside the lock."
        ),
    }
    # Observability acceptance block: obs tax on the request path,
    # online-vs-offline recall agreement, and the steady-state recompile
    # invariant over every measured window.
    obs_overhead = (
        baseline_obs["p50_obs_on"] / max(baseline_obs["p50_obs_off"], 1e-9)
    ) - 1.0
    probe_overhead = (
        baseline_probe["p50"] / max(baseline["p50"], 1e-9)
    ) - 1.0
    steady_recompiles = {
        f'{ph["phase"]}:{s}': v
        for ph in (baseline, baseline_obs, baseline_probe)
        for s, v in ph["recompiles_measured"].items()
    }
    churn_search_recompiles = {
        f'{ph["phase"]}:{s}': v
        for ph in (churn, maintained)
        for s, v in ph["recompiles_measured"].items()
        if "search" in s or s.endswith(".merge")
    }
    recall_delta = None
    if baseline_probe.get("recall_offline") is not None:
        recall_delta = abs(
            baseline_probe["recall_online"] - baseline_probe["recall_offline"]
        )
    result["observability"] = {
        "request_p50_ms_obs_off": baseline_obs["p50_obs_off"],
        "request_p50_ms_obs_on": baseline_obs["p50_obs_on"],
        "overhead_frac_request_p50": float(obs_overhead),
        "overhead_within_2pct": bool(obs_overhead <= 0.02),
        "request_p50_ms_probe_on": baseline_probe["p50"],
        "probe_overhead_frac_request_p50": float(probe_overhead),
        "recall_online": baseline_probe.get("recall_online"),
        "recall_offline": baseline_probe.get("recall_offline"),
        "recall_online_offline_abs_delta": recall_delta,
        "recall_agrees_within_0p02": (
            None if recall_delta is None else bool(recall_delta <= 0.02)
        ),
        "recall_probe_fraction": 0.25,
        # the query-side pow2-bucket invariant: zero recompiles anywhere
        # in the steady-state (no-write) phases after warmup
        "steady_state_recompiles_post_warmup": steady_recompiles,
        "steady_state_recompile_free": not steady_recompiles,
        # under churn, a compacted/sealed generation with a NOVEL row
        # count recompiles its per-segment search once — data-side shape
        # instability, the open "shape-stable sealed generations"
        # ROADMAP item; the gauge now measures it live
        "churn_search_recompiles_post_warmup": churn_search_recompiles,
        "dispatch_accounting": accounting_snapshot(),
        "noise_caveat": (
            "the tracing A/B interleaves traced/untraced requests within "
            "one phase (phase-to-phase drift on a shared-core CPU host "
            "dwarfs the tracing tax); the structural obs cost per "
            "request is one disabled-tracer check, two counter bumps "
            "per dispatch scope, and one RNG draw for the probe.  The "
            "probe phase's extra tax vs baseline is cross-phase (noisy) "
            "and includes its exact shadow scorer contending for the "
            "same host cores (accelerator deployments run it beside "
            "the device)."
        ),
    }
    print(f"\np99 ratios vs baseline: request churn={ratio_churn:.2f}x "
          f"maintained={ratio_maintained:.2f}x subprocess={ratio_sub:.2f}x "
          f"| search churn={s_ratio_churn:.2f}x "
          f"maintained={s_ratio_maintained:.2f}x "
          f"subprocess={s_ratio_sub:.2f}x "
          f"(target: maintained <= 2x)", flush=True)
    rc = result["reader_concurrency"]
    print(f"reader concurrency: search p99 in-thread="
          f"{rc['in_thread_search_p99_ms']:.1f}ms subprocess="
          f"{rc['subprocess_search_p99_ms']:.1f}ms "
          f"(improves={rc['subprocess_search_p99_improves']}), lock "
          f"exclusive only at snapshot+swap="
          f"{rc['lock_exclusive_only_at_snapshot_and_swap']}", flush=True)
    ob = result["observability"]
    print(f"obs: p50 {ob['request_p50_ms_obs_off']:.1f}ms -> "
          f"{ob['request_p50_ms_obs_on']:.1f}ms "
          f"({100 * ob['overhead_frac_request_p50']:+.1f}%; probe phase "
          f"{ob['request_p50_ms_probe_on']:.1f}ms), "
          f"recall online={ob['recall_online']} "
          f"offline={ob['recall_offline']}, steady-state recompiles="
          f"{ob['steady_state_recompiles_post_warmup'] or 0}",
          flush=True)
    with open("BENCH_serving.json", "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result, indent=2))
    print("\nwrote BENCH_serving.json", flush=True)
    return result


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv[1:])
