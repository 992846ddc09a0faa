"""Stage-1 Hamming kernel (``kernels/hamming``, ``hamming_rows``): the
least time its bytes need at the chip's HBM bandwidth over its time in the
trace, in %.  The kernel is the Mosaic call that returns int32 distances
from two uint32 sketch operands."""

from bench.harness import trace, work

_SIGNATURE = ("s32", ["u32", "u32"])


def is_kernel(name: str) -> bool:
    return trace.kernel_signature(name) == _SIGNATURE


def read(run):
    if run.trace is None:
        return None
    seconds, calls = run.trace.op_stats(is_kernel)
    if not calls:
        return None
    s = run.record["shapes"]
    need = calls * work.hamming_rows_bytes(s["queries"], s["k1"], s["dim"])
    return 100.0 * need / run.peaks()["hbm_bytes_per_s"] / seconds
