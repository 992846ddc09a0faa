"""Batched serving driver: prefill + autoregressive decode (+ retrieval).

CPU smoke:  PYTHONPATH=src python -m repro.launch.serve --arch gemma3_1b \
                --smoke --batch 4 --prompt-len 24 --gen 16 [--retrieval]

The decode loop is the same ``decode_step`` the dry-run lowers for the
decode_32k/long_500k cells; --retrieval augments each step with a
Hilbert-forest kNN-LM lookup (the paper's index as a first-class serving
feature).  ``--shards N`` row-partitions the datastore over N devices of
the ``data`` mesh (run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for a CPU smoke):
lookups then go through the sharded index's mesh-wide merged top-k.
``--churn`` exercises the streaming write path mid-decode — every few
steps the datastore absorbs an append and a delete while serving, on
either layout (the sharded store routes appends to the shard owning each
key's curve range; no rebuild-and-swap).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core.types import ForestConfig, SearchParams
from repro.index import IndexConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model
from repro.serve.engine import MaintenancePolicy
from repro.serve.retrieval import RetrievalStore, knn_lm_mix
from repro.sharding import ShardingRules


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3_1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--retrieval", action="store_true")
    ap.add_argument("--shards", type=int, default=1,
                    help="row-partition the retrieval datastore over this "
                         "many devices (1 = single-device mutable store)")
    ap.add_argument("--churn", action="store_true",
                    help="append/delete datastore entries while decoding "
                         "(streaming writes on either layout)")
    ap.add_argument("--engine", action="store_true",
                    help="serve the datastore through the RetrievalEngine: "
                         "lookups go through the admission queue and "
                         "micro-batcher, and LSM maintenance (tier merges, "
                         "compaction) runs on a background thread with an "
                         "atomic index swap instead of stalling decode")
    ap.add_argument("--wal", default=None, metavar="PATH",
                    help="attach a write-ahead log under PATH (the store's "
                         "checkpoint directory): appends/deletes are framed "
                         "+ logged before they are acknowledged, so a crash "
                         "at any instant recovers bit-equal.  See "
                         "docs/DURABILITY.md")
    ap.add_argument("--wal-sync-every", type=int, default=32,
                    help="fsync the WAL every N records (1 = every record "
                         "= full power-loss durability; the default group-"
                         "commits for <10%% append-path overhead)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="with --engine: per-request queue deadline — "
                         "requests still queued past it are failed with "
                         "DeadlineExceeded instead of dispatched")
    ap.add_argument("--lam", type=float, default=0.25)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve /metrics (Prometheus), /metrics.json and "
                         "/trace on this port (0 = ephemeral; the bound "
                         "port is printed).  See docs/OBSERVABILITY.md")
    ap.add_argument("--trace-export", default=None, metavar="PATH",
                    help="enable span tracing and write a Chrome-trace "
                         "JSON (chrome://tracing / Perfetto) on exit")
    ap.add_argument("--recall-probe", type=float, default=None,
                    metavar="FRACTION",
                    help="with --engine: sample this fraction of served "
                         "batches and score online recall@k against an "
                         "exact shadow off the query path")
    ap.add_argument("--linger", type=float, default=0.0, metavar="SECONDS",
                    help="keep the process (and --metrics-port endpoint) "
                         "alive this long after the workload finishes, so "
                         "an external scraper can read final counters")
    args = ap.parse_args()
    enable_compile_cache()

    metrics_server = None
    if args.metrics_port is not None:
        from repro.obs.http import serve_metrics

        metrics_server = serve_metrics(args.metrics_port)
        print(f"[obs] metrics endpoint at {metrics_server.url}/metrics "
              f"(also /metrics.json, /trace)", flush=True)
    if args.trace_export:
        from repro import obs

        obs.enable()

    cfg = configs.get_config(args.arch, smoke=args.smoke)
    rules = ShardingRules()
    rng = np.random.default_rng(args.seed)
    params = model.init_params(cfg, jax.random.key(args.seed))

    b, sp = args.batch, args.prompt_len
    total = sp + args.gen
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, sp)), jnp.int32)
    extra = {}
    if cfg.is_encdec:
        extra["frames"] = jnp.asarray(
            rng.normal(size=(b, cfg.enc_frames, cfg.d_model)), jnp.float32)
    if cfg.n_patches:
        extra["patches"] = jnp.asarray(
            rng.normal(size=(b, cfg.n_patches, cfg.patch_dim)), jnp.float32)

    store = None
    if args.retrieval:
        # datastore: hidden states of a reference corpus through this model
        corpus = jnp.asarray(rng.integers(0, cfg.vocab_size, (16, 64)), jnp.int32)
        cextra = {}
        if cfg.is_encdec:
            cextra["frames"] = jnp.asarray(
                rng.normal(size=(16, cfg.enc_frames, cfg.d_model)), jnp.float32)
        if cfg.n_patches:
            cextra["patches"] = jnp.asarray(
                rng.normal(size=(16, cfg.n_patches, cfg.patch_dim)), jnp.float32)
        hid, _, _ = model.forward(cfg, params, corpus, rules,
                                  return_hidden=True, **cextra)
        keys = hid[:, :-1].reshape(-1, cfg.d_model).astype(jnp.float32)
        vals = corpus[:, 1:].reshape(-1)
        fc = ForestConfig(n_trees=8, bits=4, key_bits=min(256, cfg.d_model * 4),
                          leaf_size=32)
        mesh = None
        if args.shards > 1:
            from repro.launch.mesh import data_mesh

            mesh = data_mesh(args.shards)
        # Compaction re-sorts raw keys, so the churn demo keeps them
        # resident; otherwise store_points=False serves RAM-lean (appends
        # and deletes still work on both layouts).
        store_points = args.churn
        store = RetrievalStore.build(
            keys, vals, IndexConfig(forest=fc, store_points=store_points),
            mesh=mesh, shards=args.shards,
        )
        layout = (f"sharded-mutable x{args.shards}" if store.is_sharded
                  else "mutable (single device)")
        print(f"[retrieval] datastore: {keys.shape[0]} entries, {layout}")
        if args.wal:
            from repro.checkpoint import WalConfig

            store.enable_wal(
                args.wal, WalConfig(sync_every=args.wal_sync_every)
            )
            print(f"[wal] durable writes -> {args.wal}/wal.log "
                  f"(sync_every={args.wal_sync_every})")
        if args.engine:
            # Background maintenance only makes sense when segments keep
            # their raw points (store_points tracks --churn above).
            recall_cfg = None
            if args.recall_probe:
                from repro.obs.recall import RecallProbeConfig

                recall_cfg = RecallProbeConfig(
                    fraction=args.recall_probe, seed=args.seed
                )
            engine = store.serving_engine(
                SearchParams(k1=32, k2=64, h=1, k=8),
                maintenance=MaintenancePolicy() if store_points else None,
                recall=recall_cfg,
                default_deadline_ms=args.deadline_ms,
                start=True,
            )
            print(f"[engine] {engine!r}")

    t0 = time.time()
    logits, caches = model.prefill(cfg, params, prompts, rules, **extra)
    caches = model.pad_caches(cfg, caches, total)
    print(f"[prefill] {b}x{sp} in {time.time()-t0:.2f}s")

    decode = jax.jit(
        lambda p, t, i, c: model.decode_step(cfg, p, t, i, c, rules,
                                             with_hidden=True))
    sp_params = SearchParams(k1=32, k2=64, h=1, k=8)
    tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    out_tokens = [tok]
    churned: list = []
    t0 = time.time()
    for t in range(sp, total):
        logits_t, caches, hid = decode(params, tok, jnp.int32(t), caches)
        if store is not None:
            logp = knn_lm_mix(logits_t.astype(jnp.float32),
                              hid.astype(jnp.float32), store, sp_params,
                              lam=args.lam)
            tok = jnp.argmax(logp, axis=-1)[:, None].astype(jnp.int32)
            if args.churn and (t - sp) % 4 == 0:
                # streaming writes while serving: the decoded (hidden ->
                # token) pairs join the datastore; the previous churn
                # batch is evicted (a rolling-window datastore)
                new_ids = store.append(hid.astype(jnp.float32), tok[:, 0])
                if churned:
                    store.delete(churned.pop())
                churned.append(new_ids)
        else:
            tok = jnp.argmax(logits_t, axis=-1)[:, None].astype(jnp.int32)
        out_tokens.append(tok)
    dt = time.time() - t0
    if store is not None and store.engine is not None:
        store.engine.stop(drain=True)
        snap = store.engine.metrics.snapshot()
        lat = snap["latency_ms"]
        print(f"[engine] {snap['counters']['batches']} batches / "
              f"{snap['counters']['rows_searched']} rows, "
              f"p50={lat.get('p50', 0):.1f}ms p99={lat.get('p99', 0):.1f}ms, "
              f"swaps={snap['counters']['swaps']} "
              f"(maintenance runs={snap['counters']['maintenance_runs']})")
    if store is not None and args.churn:
        rep = store.memory_report()
        print(f"[churn] live={rep['n_live']} deleted={rep['n_deleted']} "
              f"buffered={rep['n_buffered']} segments={rep['n_segments']}")
        store.compact()
        print(f"[churn] compacted -> segments={store.memory_report()['n_segments']}")
    gen = np.concatenate([np.asarray(t) for t in out_tokens], axis=1)
    print(f"[decode] {args.gen} steps x batch {b}: {1000*dt/args.gen:.0f} ms/step")
    print("[tokens]", gen[0][:16], "...")
    if args.trace_export:
        from repro import obs

        obs.default_tracer().dump(args.trace_export)
        print(f"[obs] wrote Chrome trace to {args.trace_export}", flush=True)
    if args.linger > 0:
        print(f"[obs] lingering {args.linger:.0f}s for scrapers", flush=True)
        time.sleep(args.linger)
    if metrics_server is not None:
        metrics_server.close()


if __name__ == "__main__":
    main()
