"""Device time of one Hilbert sort (``core/hilbert.py``: the key pass
``hilbert_keys`` and the sort ``lexsort_words``), in ms, over every sort
in the trace: the forest's trees, the master order and each graph order
(``knn_graph.order_and_rank``)."""


def read(run):
    if run.trace is None:
        return None
    keys_s, _ = run.trace.module_stats("jit_hilbert_keys")
    sort_s, sorts = run.trace.module_stats("jit_lexsort_words")
    return 1000.0 * (keys_s + sort_s) / sorts if sorts else None
