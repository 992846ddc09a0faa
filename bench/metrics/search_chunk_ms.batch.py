"""Device time of one fused search chunk (``core/search.py``
``fused_search_chunk``, one dispatch per chunk of queries), in ms, from
the trace's program executions."""


def read(run):
    if run.trace is None:
        return None
    seconds, n = run.trace.module_stats("jit_fused_search_chunk")
    return 1000.0 * seconds / n if n else None
