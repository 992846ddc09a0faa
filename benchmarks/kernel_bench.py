"""Kernel microbenchmarks: Hamming filter + fused 4-bit ADC distance.

On this CPU container the Pallas kernels execute in interpret mode (Python —
not timing-relevant); the numbers that matter here are (a) the jnp-oracle
throughput on CPU as a sanity floor and (b) the ANALYTIC TPU roofline for
the kernel's tiling, derived from bytes/flops per tile (see EXPERIMENTS.md
§Kernels): both kernels are HBM-bandwidth-bound on v5e, so the model is
bytes_touched / 819 GB/s.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quantize
from repro.kernels.hamming import hamming_matrix
from repro.kernels.qdist import qdist

HBM_BW = 819e9


def _time(f, *args, iters=5):
    f(*args).block_until_ready()
    t0 = time.time()
    for _ in range(iters):
        out = f(*args)
    out.block_until_ready()
    return (time.time() - t0) / iters


def main():
    rng = np.random.default_rng(0)
    print("name,cpu_us_per_call,tpu_roofline_us,bytes_per_call")

    # hamming: Q=512 queries × C=65536 candidates × 384-bit sketches
    q, c, w = 512, 65536, 12
    a = jnp.asarray(rng.integers(0, 2**32, (q, w), dtype=np.uint32))
    b = jnp.asarray(rng.integers(0, 2**32, (c, w), dtype=np.uint32))
    t = _time(lambda x, y: hamming_matrix(x, y), a, b)
    nbytes = (q * w + c * w) * 4 + q * c * 4  # reads + output
    print(f"hamming_{q}x{c}x384b,{1e6*t:.0f},{1e6*nbytes/HBM_BW:.0f},{nbytes}")

    # qdist: Q=512 × C=16384 × d=384, 4-bit codes
    cq, cc, d = 512, 16384, 384
    data = rng.normal(size=(cc, d)).astype(np.float32)
    quant = quantize.fit(jnp.asarray(data), bits=4)
    codes = quantize.encode(quant, jnp.asarray(data))
    queries = jnp.asarray(rng.normal(size=(cq, d)).astype(np.float32))
    t = _time(lambda x: qdist(x, codes, quant.centroids), queries)
    nbytes = cq * d * 4 + cc * d // 2 + cq * cc * 4  # fp32 q + packed codes + out
    print(f"qdist_{cq}x{cc}x{d},{1e6*t:.0f},{1e6*nbytes/HBM_BW:.0f},{nbytes}")

    # interpret-mode correctness spot check (kernels vs oracle) at bench shapes
    got = hamming_matrix(a[:8], b[:256], use_kernel=True)
    ref = hamming_matrix(a[:8], b[:256])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    print("kernel_interpret_check,ok,,")


if __name__ == "__main__":
    main()
