"""Back-to-back k-NN graph constructions (Task 2).

One construction runs from the points on the device to the graph on the
device: ``HilbertIndex.build`` with the configuration's forest, then
``.knn_graph`` with its graph parameters.  Constructions start while the
window lasts (at least one); ``graph_build_s`` is the whole window, to the
end of the last one started, over their number.  Set-up warms every shape
with one build and a one-order graph.

Mix keys: ``check_rows``, the rows whose neighbour lists are compared with
exact search (every row's reported distances are compared).
"""

from __future__ import annotations

import time


def run(run) -> None:
    import jax
    import numpy as np

    from bench.harness import checks, data, program, reference
    from bench.harness.trace import span
    from repro.index import HilbertIndex

    cfg, mix = run.config, run.traffic
    icfg, params = program.index_config(cfg), program.graph_params(cfg)
    base, _ = data.corpus(run.seed, cfg, 0)
    warm = HilbertIndex.build(base, icfg)
    jax.block_until_ready(warm.knn_graph(
        program.graph_params(cfg, n_orders=1)))
    del warm
    run.setup_done()

    builds = 0
    with run.traced():
        t_start = time.perf_counter()
        while True:
            with span("bench.build"):
                index = HilbertIndex.build(base, icfg)
                jax.block_until_ready(index)
            with span("bench.knn_graph"):
                graph = jax.block_until_ready(index.knn_graph(params))
            builds += 1
            del index
            if time.perf_counter() - t_start >= run.seconds:
                break
        t_end = time.perf_counter()
    run.window_done()
    run.attempted = builds
    run.metrics["graph_build_s"] = (t_end - t_start) / builds
    run.read_peak()

    ids, d2 = (np.asarray(a) for a in graph)
    del graph
    n, k = len(base), params.k
    rows = np.random.default_rng(run.seed).choice(n, mix["check_rows"],
                                                  replace=False)
    ref, _ = reference.exact_topk(base[rows], base, k + 1)
    ref = np.asarray(ref)
    ref = np.stack([r[r != i][:k] for r, i in zip(ref, rows)])
    exact = reference.pair_d2(base, base, ids)
    limits = cfg["limits"]
    run.check("recall", checks.recall(ids[rows], ref), limits["recall"],
              ">=")
    run.check("dist_gap", checks.dist_gap(d2, exact), limits["dist_gap"],
              "<=")
    run.check("bad_ids", checks.bad_ids(ids, n, self_rows=np.arange(n)), 0,
              "<=")
