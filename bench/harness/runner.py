"""One run of one cell: set-up, window, checks, and the result line.

:func:`run_cell` drives a cell in this process on whatever devices JAX
has; ``bench/run.py`` adds the command line and the refusal to run
anywhere but on a TPU.  Tests call :func:`run_cell` on the CPU at small
sizes, with the configuration and traffic given as dictionaries.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

from bench.harness import trace as trace_lib
from bench.harness.checks import Check


class Run:
    """What a traffic driver gets: the cell's files, the seed and window
    length, and the places to leave what it measured.

    A driver calls :meth:`setup_done` when set-up ends, runs the traced
    part of its window inside :meth:`traced`, calls :meth:`window_done`
    when the window ends, reads the memory peak with
    :meth:`read_peak` before it frees the program's state and runs the
    reference, and adds each number compared with :meth:`check`.
    """

    def __init__(self, name: str, config: Dict[str, Any],
                 traffic: Dict[str, Any], *, seed: int, seconds: float,
                 trace: bool, t0: Optional[float] = None):
        self.name, self.config, self.traffic = name, config, traffic
        self.seed, self.seconds, self.tracing = seed, seconds, trace
        self.t0 = time.perf_counter() if t0 is None else t0
        self.setup_s: Optional[float] = None
        self.metrics: Dict[str, float] = {}
        self.record: Dict[str, Any] = {}
        self.checks: List[Check] = []
        self.attempted = 0
        self.failed = 0
        self.peak_bytes: Optional[int] = None
        self.trace: Optional[trace_lib.Trace] = None
        self.notes: List[str] = []

    def setup_done(self) -> None:
        from repro.obs.dispatch import compiles_total, install_compile_listener

        self.setup_s = time.perf_counter() - self.t0
        install_compile_listener()
        self._compiles = compiles_total()

    def window_done(self) -> None:
        """Note how many programs compiled inside the window (there should
        be none: set-up warms every shape)."""
        from repro.obs.dispatch import compiles_total

        self.note(window_compiles=compiles_total() - self._compiles)

    @contextlib.contextmanager
    def traced(self):
        """Profile the body when this is a ``--trace 1`` run."""
        with trace_lib.capture(self.tracing) as out:
            yield
        try:
            if out["path"]:
                t = time.perf_counter()
                self.trace = trace_lib.Trace.from_file(
                    out["path"], keep_op=trace_lib.is_kernel)
                self.note(trace_bytes=os.path.getsize(out["path"]),
                          trace_read_s=time.perf_counter() - t)
        finally:
            trace_lib.discard(out)

    def read_peak(self) -> None:
        import jax

        peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                 for d in jax.local_devices()]
        self.peak_bytes = max(peaks)

    def peaks(self) -> dict:
        """The published peaks of the chip this run is on."""
        import jax

        from bench.harness.peaks import peaks

        return peaks(jax.local_devices()[0].device_kind)

    def check(self, name: str, value: float, limit: float, op: str) -> None:
        self.checks.append(Check(name, float(value), float(limit), op))

    def note(self, **kv) -> None:
        """An informational line on standard error (not a metric)."""
        self.notes.append(" ".join(f"{k}={v!r}" for k, v in kv.items()))

    @property
    def correct(self) -> bool:
        return bool(self.checks) and self.failed == 0 and all(
            c.ok for c in self.checks)


def run_cell(cell_entry: Dict[str, Any], config: Dict[str, Any],
             traffic: Dict[str, Any], driver, readers, end_to_end, *,
             seed: int, seconds: float, trace: bool,
             t0: Optional[float] = None) -> Dict[str, Any]:
    """Run one cell and return its result line as a dict."""
    import jax

    run = Run(cell_entry["name"], config, traffic, seed=seed,
              seconds=seconds, trace=trace, t0=t0)
    driver.run(run)
    if run.setup_s is None or run.peak_bytes is None:
        raise RuntimeError("driver did not mark set-up or read the peak")
    run.metrics["setup_s"] = run.setup_s
    devices = jax.local_devices()
    metrics: Dict[str, Any] = {}
    if not trace:
        for m in end_to_end:
            metrics[m["name"]] = {"value": run.metrics[m["name"]],
                                  "unit": m["unit"]}
    else:
        for m, reader in readers:
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.peak_bytes}
    line: Dict[str, Any] = {"correct": run.correct,
                            "attempted": run.attempted,
                            "failed": run.failed,
                            "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        line["breakdown"] = run.trace.breakdown()
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in run.checks}
    for n in run.notes:
        print(n, file=sys.stderr)
    for c in run.checks:
        print(c.line(), file=sys.stderr)
    sys.stderr.flush()
    return line


def dumps(line: Dict[str, Any]) -> str:
    return json.dumps(line, allow_nan=False)
