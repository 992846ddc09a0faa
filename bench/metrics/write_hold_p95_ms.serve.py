"""95th percentile of how long one write (an insert or delete) holds the
engine's serve lock exclusively, in ms, over the window: the program's
``engine_rwlock_write_hold_ms`` recorder (``serve/rwlock.py``)."""

from bench.harness.counters import window_percentile


def read(run):
    return window_percentile(run, "engine_rwlock_write_hold_ms", 95.0)
