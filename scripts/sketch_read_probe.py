"""Time stage 1's sketch read + Hamming kernel per tree, by route, on a TPU.

    PYTHONPATH=src python scripts/sketch_read_probe.py [out.jsonl]

For 2^20 and 2^22 random 12-word sketches and Q = 8, 64 and 512 queries of
k1 = 1420 random candidates each, runs 32 trees of each route inside one
jitted scan and prints, per route, the time per tree of five repeats:

* ``plain`` — an (n, 12) table's row gather (stored point-minor on a TPU);
* ``vmem`` — the blocked table copied into VMEM (``sketch_table_kernel``);
* ``hbm_slots`` — XLA's whole-row gather and ``sketch_slots_kernel``;
* ``hbm_xla`` — XLA's whole-row gather and its own slot select.

Each route's distances are checked against ``plain``'s.  The last line
gives the best time per tree of each; ``ops.ROW_READ_SAVING_S`` is read
from these: the rows read at which ``vmem`` and ``hbm_slots`` break even,
over the table's copy time at the chip's peak HBM bandwidth.
"""

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import sketch
from repro.kernels.hamming import ops
from repro.kernels.hamming.kernel import sketch_slots_kernel, sketch_table_kernel

W, K1, T = 12, 1420, 32


def _read(route, w=W):
    def read(table, pos):
        if route == "plain":
            return jnp.swapaxes(table[pos], 1, 2)
        s = sketch.slot_words(w)
        bk = ops._rows_bk(pos.shape[1])
        if route == "vmem":
            rows, slots = sketch.row_and_slot(table, pos, w)
            return sketch_table_kernel(rows, slots, table, w=w, s=s, bk=bk)
        if route == "hbm_slots":
            rows, slots = sketch.gather_rows(table, pos, w)
            return sketch_slots_kernel(slots, rows, w=w, s=s, bk=bk)
        return jnp.swapaxes(sketch.read_blocked(table, pos, w), 1, 2)
    return read


def _programs(route):
    read = _read(route)

    @jax.jit
    def trees(table, qsk, pos_all):
        def body(acc, pos):
            hd = ops.hamming_rows_word_major(qsk, read(table, pos))
            return acc + jnp.sum(hd[:, :K1]), None
        return lax.scan(body, jnp.int32(0), pos_all)[0]

    @jax.jit
    def one(table, qsk, pos):
        return ops.hamming_rows_word_major(qsk, read(table, pos))
    return trees, one


def main(out_path=None):
    out = open(out_path, "w") if out_path else None

    def log(line):
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    results = {}
    for n in (1 << 20, 1 << 22):
        sk = jax.random.bits(jax.random.PRNGKey(n), (n, W), jnp.uint32)
        blocked = sketch.block_sketches(sk)
        for q in (8, 64, 512):
            kp = -(-K1 // 512) * 512
            pos_all = jax.random.randint(jax.random.PRNGKey(q), (T, q, kp),
                                         0, n, jnp.int32)
            qsk = jax.random.bits(jax.random.PRNGKey(q + 1), (q, W),
                                  jnp.uint32)
            ref = None
            for route in ("plain", "vmem", "hbm_slots", "hbm_xla"):
                if route == "vmem" and blocked.nbytes > (96 << 20):
                    continue  # no room in a v5e's VMEM
                table = sk if route == "plain" else blocked
                trees, one = _programs(route)
                t0 = time.perf_counter()
                jax.block_until_ready(trees(table, qsk, pos_all))
                first = time.perf_counter() - t0
                times = []
                for _ in range(5):
                    t0 = time.perf_counter()
                    jax.block_until_ready(trees(table, qsk, pos_all))
                    times.append((time.perf_counter() - t0) / T * 1e3)
                hd = np.asarray(one(table, qsk, pos_all[0]))[:, :K1]
                ref = hd if ref is None else ref
                rec = dict(n=n, q=q, route=route, first_s=first,
                           ms_per_tree=times,
                           equal_plain=bool((hd == ref).all()),
                           code_picks_vmem=ops.table_in_vmem(
                               blocked.nbytes, q * kp, 128))
                results[f"{n}_{q}_{route}"] = rec
                log(json.dumps(rec))
    log("FINAL " + json.dumps(
        {k: min(v["ms_per_tree"]) for k, v in results.items()}))


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else None)
