"""Device time of one graph order's merge (``core/knn_graph.py``
``merge_order``), in ms, from the trace's program executions."""


def read(run):
    if run.trace is None:
        return None
    seconds, n = run.trace.module_stats("jit_merge_order")
    return 1000.0 * seconds / n if n else None
