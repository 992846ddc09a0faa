#!/usr/bin/env python3
"""Find the highest request rate an open-loop cell sustains, on the chip.

    python bench/sweep.py --workload pubmed23.serve_ycsb_d --seconds 15 \
        --rates 20 40 60 80 100 --seed 5

One set-up, then one window per rate (each on its own schedule from the
seed), printing per rate one JSON line: requests, latency percentiles
from when each was due, mean rows per engine batch, how late the
generator ran, and how long the last answers took after the last arrival
(a backlog that grows through the window shows there).  The cell's rate
in its mix file is set from this once, at about four fifths of the
highest sustained rate.  Exits 1 without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.harness import runner, schedule, spec
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("sweep: JAX finds no TPU", file=sys.stderr)
        return 1
    cell = spec.Cell(spec.load_spec(), args.workload)
    mix = cell.traffic
    plans = [schedule.open_loop(args.seed + i, rate=r, seconds=args.seconds,
                                shares=mix["shares"])
             for i, r in enumerate(args.rates)]
    run = runner.Run(cell.name, cell.config, mix, seed=args.seed,
                     seconds=args.seconds, trace=False)
    serving = cell.driver().Serving(run, sum(
        int((k == "insert").sum()) for _, k in plans) * mix["insert_rows"])
    for rate, (due, kinds) in zip(args.rates, plans):
        w = serving.window(run, due, kinds, traced=False)
        lat = w["latency_ms"]
        last_due = w["t_start"] + due[-1]
        print(json.dumps({
            "rate_per_s": rate, "requests": len(w["reqs"]),
            "failed": len(w["reqs"]) - len(w["ok"]),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p99_ms": float(np.percentile(lat, 99)),
            "batch_rows_mean": w["rows_searched"] / max(1, w["batches"]),
            "generator_late_ms": schedule.lateness_ms(due, w["sent"]),
            "drain_s": w["t_end"] - last_due,
            "recompiles": w["recompiles"]}), flush=True)
    serving.engine.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
