"""Mean rows per engine micro-batch over the window: the engine's
``rows_searched`` counter over its ``batches`` counter (``serve/engine.py``,
``EngineMetrics``)."""


def read(run):
    batches = run.record.get("batches")
    return run.record["rows_searched"] / batches if batches else None
