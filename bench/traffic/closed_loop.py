"""Closed-loop batch search through the serving engine (Task 1).

Mix keys: ``clients`` threads each send a request of ``rows_per_request``
queries drawn from a ``query_pool`` of held-out queries, wait for its
answer, and send the next, until ``--seconds`` have passed; the engine
forms micro-batches of at most ``max_batch`` rows on ``backend``.
``trace_seconds`` caps the traced part of a ``--trace 1`` run.

``search_qps`` is every query row answered over the time from the first
submit to the completion of the last request submitted inside the window.
"""

from __future__ import annotations

import threading
import time


def run(run) -> None:
    import numpy as np

    from bench.harness import checks, data, program, reference
    from bench.harness.trace import span
    from repro.index import HilbertIndex
    from repro.serve.engine import RetrievalEngine

    cfg, mix = run.config, run.traffic
    k, rpr = cfg["search"]["k"], mix["rows_per_request"]
    base, queries = data.corpus(run.seed, cfg, mix["query_pool"])
    index = HilbertIndex.build(base, program.index_config(cfg))
    engine = RetrievalEngine(index, program.search_params(cfg),
                             max_batch=mix["max_batch"],
                             backend=mix["backend"], maintenance=None,
                             start=True)
    q_host = np.asarray(queries)
    engine.submit(q_host[:rpr]).result()   # every shape the window uses
    run.setup_done()

    answers, lock = [], threading.Lock()
    t_start = time.perf_counter()
    deadline = t_start + run.seconds

    def client(c: int) -> None:
        rng = np.random.default_rng([run.seed, c])
        while time.perf_counter() < deadline:
            rows = rng.choice(len(q_host), rpr, replace=False)
            with span("bench.submit"):
                ticket = engine.submit(q_host[rows])
            with span("bench.wait"):
                try:
                    ticket.result()
                except Exception:  # noqa: BLE001 - counted as failed
                    pass
            with lock:
                answers.append((rows, ticket))

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(mix["clients"])]
    with run.traced():
        for t in threads:
            t.start()
        if run.tracing:
            time.sleep(min(run.seconds, mix["trace_seconds"]))
    for t in threads:
        t.join()
    engine.stop()
    run.window_done()
    t_end = max(t.completed_at for _, t in answers)
    done = [(r, t) for r, t in answers if t.error is None]
    run.attempted, run.failed = len(answers), len(answers) - len(done)
    run.metrics["search_qps"] = sum(len(r) for r, _ in done) / (
        t_end - t_start)
    run.record["shapes"] = {
        "queries": rpr, "k1": cfg["search"]["k1"], "k2": cfg["search"]["k2"],
        "h": cfg["search"]["h"], "dim": cfg["dim"],
        "levels": 1 << cfg["quantizer"]["bits"]}
    run.read_peak()

    rows = np.concatenate([r for r, _ in done])
    ids = np.concatenate([t.ids for _, t in done])
    d2 = np.concatenate([t.dists for _, t in done])
    del engine, index
    ref, _ = reference.exact_topk(queries, base, k)
    ref = np.asarray(ref)[rows]
    exact = reference.pair_d2(queries[rows], base, ids)
    limits = cfg["limits"]
    run.check("recall", checks.recall(ids, ref), limits["recall"], ">=")
    run.check("dist_gap", checks.dist_gap(d2, exact), limits["dist_gap"],
              "<=")
    run.check("bad_ids", checks.bad_ids(ids, len(base)), 0, "<=")
