"""Median wait of a search request from submit to batch formation
(``SearchTicket.queue_wait_ms``, ``serve/engine.py``), in ms, over the
window's requests."""

import statistics


def read(run):
    waits = run.record.get("queue_wait_ms")
    return statistics.median(waits) if waits else None
