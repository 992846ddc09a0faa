"""Seconds of the index build's forest phase (the Hilbert sorts of every
tree) in set-up: the last ``index_build_ms{phase="forest"}`` sample before
the window (``index/facade.py`` ``build_with_timings``), over 1000."""

from bench.harness.counters import last_before_window


def read(run):
    ms = last_before_window(run, "index_build_ms", phase="forest")
    return None if ms is None else ms / 1000.0
