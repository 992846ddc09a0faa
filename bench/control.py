#!/usr/bin/env python3
"""Read the control's numbers for a cell on the chip.

    python bench/control.py --workload pubmed23.task1_batch \
        --seconds 10 --seeds 11 12 13

Runs the cell through the benchmark's own drivers and checks, one seed
after another in this process, with the configuration's control (the
reference one precision step down, ``bench/harness/controls.py``) in the
program's place, and prints each run's numbers and limits as one JSON
line.  ``correct`` has to read false on every seed.  Exits 1 without a
TPU.
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
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.harness import controls, runner, spec
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: JAX finds no TPU", file=sys.stderr)
        return 1
    cell = spec.Cell(spec.load_spec(), args.workload)
    driver = cell.driver()
    for seed in args.seeds:
        with controls.installed(cell.config["control"]):
            line = runner.run_cell(cell.entry, cell.config, cell.traffic,
                                   driver, [], cell.end_to_end, seed=seed,
                                   seconds=args.seconds, trace=False)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": cell.config["control"],
                          "correct": line["correct"],
                          "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
