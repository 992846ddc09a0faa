#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload pubmed23.task1_batch --seed 7 \
        --seconds 30 --trace 0

Run from the root of a checkout.  The cell is an entry of ``workloads`` in
``BENCHMARK.json``; its configuration, traffic mix, traffic driver and
per-layer metric readers are found by name (see ``bench/README.md``).
Standard output ends with one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and ``checks`` (each
number compared for ``correct``, with its limit; also the last lines of
standard error).  Exits 1 without printing a result when JAX finds no TPU
or fewer chips than the cell asks for, and 2 when the program under test
or the cell's files are missing.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv) -> int:
    args = _parse(argv)
    if args.seed < 0:
        print("bench: --seed must be >= 0", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.harness import spec as spec_lib
    from bench.harness import runner

    try:
        cell = spec_lib.Cell(spec_lib.load_spec(), args.workload)
        driver, readers = cell.driver(), cell.readers()
    except (OSError, KeyError, ValueError) as e:
        print(f"bench: cannot load cell {args.workload!r}: {e}",
              file=sys.stderr)
        return 2
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError:
        print("bench: the program under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 2
    enable_compile_cache()
    import jax

    # Cache every program, however quickly it compiled, so that only the
    # first run of a cell in a checkout compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX finds no TPU (platform {devices[0].platform!r}); "
              "the benchmark runs only on the chip", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return 1
    line = runner.run_cell(cell.entry, cell.config, cell.traffic, driver,
                           readers, cell.end_to_end, seed=args.seed,
                           seconds=args.seconds, trace=bool(args.trace),
                           t0=T0)
    print(runner.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
