"""95th percentile of a search batch's wait for the engine's shared serve
lock (behind inserts), in ms, over the window: the program's
``engine_rwlock_read_wait_ms`` recorder (``serve/engine.py``)."""

from bench.harness.counters import window_percentile


def read(run):
    return window_percentile(run, "engine_rwlock_read_wait_ms", 95.0)
