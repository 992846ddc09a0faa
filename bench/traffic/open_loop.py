"""Open-loop serving with writes through the engine (YCSB-style mixes).

Operations arrive on a schedule fixed by the seed
(:func:`bench.harness.schedule.open_loop`) at ``rate_per_s`` for
``--seconds``, whether or not earlier ones have finished.  Kinds, by
``shares``:

* ``search`` — one request of ``query_rows`` queries drawn from a
  ``query_pool`` of held-out queries;
* ``read_latest`` — one request that reads back one of the
  ``read_latest_window`` most recently acknowledged inserts, its own
  vector as the query: the answer has to hold the inserted id;
* ``insert`` — ``insert_rows`` new rows, applied in arrival order by one
  writer thread through ``RetrievalEngine.insert``.

Set-up adopts the built index into a ``MutableHilbertIndex`` with a
``buffer_capacity``-row write buffer, applies ``setup_inserts`` inserts
and ``setup_deletes`` deletes, and warms every batch size up to
``max_batch``.  ``request_p95_ms`` times every search request from when it
was due to its result; a failed request counts as answered at the end of
the window.  ``trace_seconds`` caps the traced part of a ``--trace 1``
run; ``drain_timeout_s`` bounds the wait for the last answers.
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time


class Serving:
    """The engine over a mutable index after set-up, and the windows run
    on it (``bench/sweep.py`` runs several windows on one set-up)."""

    def __init__(self, run, window_inserts: int):
        import numpy as np

        from bench.harness import data, program
        from repro.index import HilbertIndex, MutableHilbertIndex
        from repro.serve.engine import RetrievalEngine

        cfg, mix = run.config, run.traffic
        self.mix = mix
        pool, n_set = mix["query_pool"], mix["setup_inserts"]
        self.base, extra = data.corpus(run.seed, cfg,
                                       pool + n_set + window_inserts)
        self.n = n = len(self.base)
        self.rng = np.random.default_rng(run.seed)
        index = HilbertIndex.build(self.base, program.index_config(cfg))
        mut = MutableHilbertIndex.from_index(
            index, buffer_capacity=mix["buffer_capacity"])
        self.engine = RetrievalEngine(
            mut, program.search_params(cfg), max_batch=mix["max_batch"],
            backend=mix["backend"], maintenance=None)
        rows = np.asarray(extra)
        self.q_pool = rows[:pool]
        self.r_set = rows[pool:pool + n_set]
        self.r_win = rows[pool + n_set:]
        self.engine.insert(self.r_set)
        self.deleted = self.rng.choice(n, mix["setup_deletes"],
                                       replace=False)
        self.engine.delete(self.deleted)
        # Every batch size: the program compiles per row count, not per
        # power-of-two bucket (it pads and slices eagerly around its
        # bucketed dispatch).
        for m in range(1, mix["max_batch"] + 1):
            self.engine.search(self.q_pool[:m])
        self.engine.start()
        self.acked = [(n + i, -np.inf) for i in range(n_set)]  # (id, ack)
        self.used = 0                          # rows of r_win inserted

    def row(self, ext_id: int):
        n, n_set = self.n, len(self.r_set)
        return (self.r_set[ext_id - n] if ext_id < n + n_set
                else self.r_win[ext_id - n - n_set])

    def window(self, run, due, kinds, *, traced: bool) -> dict:
        """Drive one window of the schedule ``(due, kinds)``; returns what
        happened in it."""
        import numpy as np

        from bench.harness.trace import span
        from repro.obs.dispatch import accounting_delta, accounting_snapshot

        mix, engine, rng = self.mix, self.engine, self.rng
        latest = mix["read_latest_window"]
        writes: "queue.Queue" = queue.Queue()
        reqs, sent, write_errors = [], np.zeros(len(due)), []
        clock = {}

        def writer() -> None:
            while True:
                item = writes.get()
                if item is None:
                    return
                try:
                    with span("bench.insert"):
                        ids = engine.insert(item)
                except Exception as e:  # noqa: BLE001 - counted as failed
                    write_errors.append(e)
                    continue
                now = time.perf_counter()
                self.acked.extend((int(i), now) for i in ids)

        def generator() -> None:
            t_start = clock["start"]
            for i, (at, kind) in enumerate(zip(due, kinds)):
                wait = t_start + at - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                sent[i] = time.perf_counter() - t_start
                if kind == "insert":
                    m = mix["insert_rows"]
                    writes.put(self.r_win[self.used:self.used + m])
                    self.used += m
                    continue
                target = -1
                if kind == "read_latest":
                    pick = rng.integers(min(latest, len(self.acked)))
                    target = self.acked[-1 - pick][0]
                    q = self.row(target)[None]
                else:
                    q = self.q_pool[rng.integers(len(self.q_pool),
                                                 size=mix["query_rows"])]
                with span("bench.submit"):
                    ticket = engine.submit(q)
                reqs.append((at, q, target, ticket))

        acct0 = accounting_snapshot()
        c0 = {c: engine.metrics.counter(c)
              for c in ("batches", "rows_searched")}
        threads = [threading.Thread(target=writer, daemon=True),
                   threading.Thread(target=generator, daemon=True)]
        with run.traced() if traced else contextlib.nullcontext():
            clock["start"] = t_start = time.perf_counter()
            for t in threads:
                t.start()
            if traced and run.tracing:
                time.sleep(min(run.seconds, mix["trace_seconds"]))
        threads[1].join()
        writes.put(None)
        threads[0].join()
        for *_, ticket in reqs:
            try:
                ticket.result(timeout=mix["drain_timeout_s"])
            except Exception:  # noqa: BLE001 - counted as failed
                pass
        t_end = time.perf_counter()
        delta = accounting_delta(acct0, accounting_snapshot())
        ok = [r for r in reqs if r[3].done and r[3].error is None]
        lat = np.array([1000.0 * ((t.completed_at if t.done and t.error is
                                   None else t_end) - t_start - at)
                        for at, _, _, t in reqs])
        return {
            "reqs": reqs, "ok": ok, "latency_ms": lat,
            "write_errors": write_errors, "t_start": t_start,
            "t_end": t_end, "due": due, "sent": sent,
            "recompiles": sum(delta["recompiles_by_site"].values()),
            "batches": engine.metrics.counter("batches") - c0["batches"],
            "rows_searched": (engine.metrics.counter("rows_searched")
                              - c0["rows_searched"]),
            "queue_wait_ms": [t.queue_wait_ms for *_, t in reqs
                              if t.queue_wait_ms is not None],
        }

    def check(self, run, w: dict) -> None:
        """Free the program, then compare every answered request with the
        reference over the rows live when it completed."""
        import jax.numpy as jnp
        import numpy as np

        from bench.harness import checks, reference

        k = run.config["search"]["k"]
        ok, n, n_set = w["ok"], self.n, len(self.r_set)
        ids = np.concatenate([r[3].ids for r in ok])
        d2 = np.concatenate([r[3].dists for r in ok])
        qs = np.concatenate([r[1] for r in ok])
        done_at = np.concatenate([np.full(len(r[1]), r[3].completed_at)
                                  for r in ok])
        targets = np.concatenate([np.full(len(r[1]), r[2]) for r in ok])
        live_ids = np.array([i for i, _ in self.acked])
        ack_at = np.array([t for _, t in self.acked])
        if not np.array_equal(live_ids, np.arange(n, n + len(live_ids))):
            raise RuntimeError("inserts were not given consecutive ids")
        self.engine.stop()
        del self.engine
        # Rows by external id: the base, then inserts in acknowledgement
        # order.
        corpus = jnp.concatenate([self.base, jnp.asarray(self.r_set),
                                  jnp.asarray(self.r_win[:self.used])])
        dead = np.zeros(len(corpus), bool)
        dead[self.deleted] = True
        valid = jnp.asarray(~dead & (np.arange(len(corpus)) < n + n_set))
        q_dev = jnp.asarray(qs)
        ref_i, ref_d = (np.asarray(a) for a in reference.exact_topk(
            q_dev, corpus, k, valid=valid))
        # An insert acknowledged in the window counts for a request only
        # if it was acknowledged before the request completed.
        win = np.arange(n + n_set, len(corpus))
        if len(win):
            wd = reference.pair_d2(q_dev, corpus,
                                   np.broadcast_to(win, (len(qs), len(win))))
            wd = np.where(ack_at[None, n_set:] <= done_at[:, None], wd,
                          np.inf)
            all_i = np.concatenate([ref_i, np.broadcast_to(win, wd.shape)],
                                   1)
            all_d = np.concatenate([ref_d, wd], 1)
            pick = np.argsort(all_d, axis=1, kind="stable")[:, :k]
            ref_i = np.take_along_axis(all_i, pick, 1)
        exact = reference.pair_d2(q_dev, corpus, ids)
        limits = run.config["limits"]
        run.check("recall", checks.recall(ids, ref_i), limits["recall"],
                  ">=")
        run.check("dist_gap", checks.dist_gap(d2, exact),
                  limits["dist_gap"], "<=")
        run.check("bad_ids", checks.bad_ids(ids, len(corpus), dead=dead), 0,
                  "<=")
        reads = targets >= 0
        miss = sum(t not in row for t, row in zip(targets[reads],
                                                  ids[reads]))
        run.check("readback_miss", miss, 0, "<=")


def run(run) -> None:
    import numpy as np

    from bench.harness import schedule

    mix = run.traffic
    due, kinds = schedule.open_loop(run.seed, rate=mix["rate_per_s"],
                                    seconds=run.seconds,
                                    shares=mix["shares"])
    serving = Serving(run, int((kinds == "insert").sum())
                      * mix["insert_rows"])
    run.setup_done()
    w = serving.window(run, due, kinds, traced=True)
    run.window_done()
    run.record.update(
        window_recompiles=w["recompiles"], batches=w["batches"],
        rows_searched=w["rows_searched"], queue_wait_ms=w["queue_wait_ms"])
    run.attempted = len(due)
    run.failed = len(w["reqs"]) - len(w["ok"]) + len(w["write_errors"])
    run.metrics["request_p95_ms"] = float(np.percentile(w["latency_ms"], 95))
    run.note(requests=len(w["reqs"]), inserts=len(due) - len(w["reqs"]),
             generator_late_ms=schedule.lateness_ms(due, w["sent"]),
             window_s=w["t_end"] - w["t_start"])
    run.read_peak()
    serving.check(run, w)
