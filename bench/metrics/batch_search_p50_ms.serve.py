"""Median search execution per engine micro-batch, inside the serve lock,
in ms, over the window: the program's ``engine_search_ms`` recorder
(``serve/engine.py``)."""

from bench.harness.counters import window_percentile


def read(run):
    return window_percentile(run, "engine_search_ms", 50.0)
