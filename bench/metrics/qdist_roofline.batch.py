"""Stage-2 distance kernel (``kernels/qdist``,
``qdist_windows_from_packed``): the least time its bytes need at the
chip's HBM bandwidth over its time in the trace, in %.  The kernel is the
Mosaic call that returns float32 distances from float32 queries, uint32
packed codes and float32 centroids."""

from bench.harness import trace, work

_SIGNATURE = ("f32", ["f32", "u32", "f32"])


def is_kernel(name: str) -> bool:
    return trace.kernel_signature(name) == _SIGNATURE


def read(run):
    if run.trace is None:
        return None
    seconds, calls = run.trace.op_stats(is_kernel)
    if not calls:
        return None
    s = run.record["shapes"]
    need = calls * work.qdist_windows_bytes(s["queries"], s["k2"], s["h"],
                                            s["dim"], s["levels"])
    return 100.0 * need / run.peaks()["hbm_bytes_per_s"] / seconds
