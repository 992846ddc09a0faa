"""Ahead-of-time compiles of the main-path kernels for a TPU v5e.

Nothing here runs on a chip: each test lowers and compiles for one device of
a *described* ``v5e:2x2`` topology, so the TPU compiler (Mosaic for the
Pallas kernels) refuses here whatever it would refuse on the chip — block
shapes that break the (8, 128) tiling, VMEM overflow, unsupported
reductions.  Shapes are the paper's Table-1 widths: d=384 (12 sketch words,
48 packed code words), k1=1420, k2=370, h=2, k=30, 160 trees.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, and the suite runs under several
workers that all import this file.
"""

import base64
import contextlib
import json
import os
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import SingleDeviceSharding

from repro.configs import pubmed23
from repro.core import forest as forest_lib
from repro.core import hilbert
from repro.core import quantize
from repro.core import search as search_lib
from repro.index import IndexConfig
from repro.kernels.hamming import hamming_rows
from repro.kernels.hamming import ops as hamming_ops
from repro.kernels.hamming.kernel import (sketch_table_kernel,
                                          sketch_table_vmem_bytes)
from repro.kernels.qdist import qdist_windows_from_packed

D = pubmed23.DIM
W_SKETCH = D // 32          # 12
W_CODES = D // 8            # 48
Q = 128
ROW1 = pubmed23.TABLE1[0]   # k1=1420, k2=370, h=2, k=30
N = 1 << 20
QUERY_CHUNK = IndexConfig().query_chunk
HBM_V5E = 16 << 30
# memory_report()["resident_bytes"] of the 2^20-row pubmed23 index on a
# TPU.  Its sketch table is blocked: 12-word sketches in 16-word slots, 8 to
# a row (the device bytes an (n, 12) table takes there too).
RESIDENT_2_20 = 2_652_837_888
SKETCH_ROWS = N * 16 // 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back without one, so
    # keep these compiles out of any persistent cache.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _traced_for(sharding):
    """Trace as for the sharding's chip: what the program asks of the chip
    while tracing (``pltpu.get_tpu_info()``, e.g. its VMEM) is then the
    described v5e's, not the host's."""
    dev = next(iter(sharding.device_set))
    return jax.sharding.use_abstract_mesh(jax.sharding.AbstractMesh(
        (1,), ("chip",),
        abstract_device=jax.sharding.AbstractDevice(dev.device_kind, 1)))


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("k1", [48, ROW1.k1])
def test_hamming_rows_compiles_for_v5e(one_chip, k1):
    _compile(
        lambda q, c: hamming_rows(q, c, use_kernel=True),
        _spec((Q, W_SKETCH), jnp.uint32, one_chip),
        _spec((Q, k1, W_SKETCH), jnp.uint32, one_chip),
    )


def test_qdist_windows_compiles_for_v5e(one_chip):
    c = ROW1.k2 * (2 * ROW1.h + 1)  # 1850, padded inside the wrapper
    _compile(
        lambda q, p, cent: qdist_windows_from_packed(
            q, p, cent, d=D, use_kernel=True),
        _spec((Q, D), jnp.float32, one_chip),
        _spec((Q, c, W_CODES), jnp.uint32, one_chip),
        _spec((D, 16), jnp.float32, one_chip),
    )


def _lower_search_chunk(sharding, use_kernels, *, n=N, trees=None,
                        q=QUERY_CHUNK, row=ROW1):
    """The fused single-device search chunk over ``n`` rows, lowered."""
    fcfg = pubmed23.FOREST
    t = trees or fcfg.n_trees
    n_dir = -(-n // fcfg.leaf_size)
    key_w = -(-fcfg.key_bits // 32)

    def chunk(queries, forest, master_rank, sketches, codes, master_order,
              quant):
        return search_lib.fused_search_chunk(
            queries, forest.orders, forest.directories, forest.lo, forest.hi,
            forest.perms, forest.flips, master_rank, sketches, codes,
            master_order, quant,
            bits=fcfg.bits, key_bits=fcfg.key_bits, leaf_size=fcfg.leaf_size,
            k1=row.k1, k2=row.k2, h=row.h, k=row.k,
            use_kernels=use_kernels,
        )

    s = sharding
    forest = forest_lib.HilbertForest(
        perms=_spec((t, D), jnp.int32, s),
        flips=_spec((t, D), jnp.bool_, s),
        orders=_spec((t, n), jnp.int32, s),
        directories=_spec((t, n_dir, key_w), jnp.uint32, s),
        lo=_spec((D,), jnp.float32, s),
        hi=_spec((D,), jnp.float32, s),
    )
    quant = quantize.Quantizer(
        boundaries=_spec((D, 15), jnp.float32, s),
        centroids=_spec((D, 16), jnp.float32, s),
    )
    with _traced_for(s):
        return jax.jit(chunk).lower(
            _spec((q, D), jnp.float32, s), forest, _spec((n,), jnp.int32, s),
            _spec((n * 16 // 128, 128), jnp.uint32, s),
            _spec((n, W_CODES), jnp.uint32, s), _spec((n,), jnp.int32, s),
            quant,
        )


def _compile_search_chunk(sharding, use_kernels):
    """The fused single-device search chunk at the default query chunk."""
    compiled = _lower_search_chunk(sharding, use_kernels).compile()
    # The chunk's scratch must fit a 16 GiB v5e beside two resident 2^20-row
    # indexes (an epoch swap holds the old and the new one).
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp + 2 * RESIDENT_2_20 < HBM_V5E, temp
    return compiled.as_text(), temp


@pytest.fixture(scope="module")
def pallas_chunk(one_chip):
    """The fused chunk's Pallas route at Table-1 shapes, compiled once."""
    return _compile_search_chunk(one_chip, True)


def test_fused_search_chunk_compiles_for_v5e(pallas_chunk):
    text, temp = pallas_chunk
    assert "tpu_custom_call" in text
    assert temp < 2 << 30, temp


def _hlo_shape(text: str, name: str) -> str:
    """The result type of instruction ``%name`` in optimized HLO text."""
    m = re.search(r"%" + re.escape(name) + r" = (\S+) ", text)
    assert m, name
    return m.group(1)


def _elements(shape: str) -> int:
    dims = re.match(r"\w+\[([\d,]*)\]", shape).group(1)
    return int(np.prod([int(x) for x in dims.split(",") if x]))


def _kernel_signature(text: str, name: str):
    """Result type and operand types (with layouts) of Mosaic kernel
    ``name`` in optimized HLO text."""
    m = re.search(
        r"%" + name + r"[\w.]* = (\w+)\[.*?custom_call_target="
        r'"tpu_custom_call", operand_layout_constraints='
        r"\{((?:[^{}]|\{[^{}]*\})*)\}", text)
    assert m, name
    return m.group(1), re.findall(r"(\w+\[[\d,]*\]\{[\d,]*)\}", m.group(2))


def _assert_no_point_minor_read(text: str, wide: int):
    """No gather reads a sketch's words one by one, no copy relays a
    tensor as wide as one 128-lane row per candidate, and the Hamming
    kernel still returns s32 from two u32 operands."""
    gathers = re.findall(
        r"gather\(%([\w.\-]+), [^)]*\).*?slice_sizes=\{([\d,]+)\}", text)
    assert not [g for g in gathers if g[1] == f"1,{W_SKETCH}"]
    copies = re.findall(r"= (\S+) copy\(", text)
    assert not [c for c in copies if _elements(c) >= wide], copies
    result, operands = _kernel_signature(text, "hamming_rows")
    assert result == "s32"
    assert [op.split("[")[0] for op in operands] == ["u32", "u32"], operands
    return gathers


def test_stage1_reads_whole_sketch_rows(pallas_chunk):
    """At Table-1 shapes the blocked table (64 MiB) is read from VMEM by
    the ``sketch_table`` kernel, as the row-major ``{1,0}`` table the index
    holds, and nothing reads a candidate's words one by one."""
    text, _ = pallas_chunk
    _assert_no_point_minor_read(text, QUERY_CHUNK * ROW1.k1 * 128)
    result, operands = _kernel_signature(text, "sketch_table")
    assert result == "u32"
    assert operands[-1] == f"u32[{SKETCH_ROWS},128]{{1,0", operands


def _lower_stage1(sharding, use_kernels, n):
    """One tree's stage 1 at Table-1 shapes over a blocked table of ``n``
    rows, compiled for the v5e; the optimized HLO text."""
    fcfg, s, q = pubmed23.FOREST, sharding, QUERY_CHUNK
    n_dir = -(-n // fcfg.leaf_size)
    key_w = -(-fcfg.key_bits // 32)
    with _traced_for(s):
        lowered = jax.jit(lambda *a: search_lib.stage1_tree_merge(
            *a, bits=fcfg.bits, key_bits=fcfg.key_bits,
            leaf_size=fcfg.leaf_size, k1=ROW1.k1, k2=ROW1.k2,
            use_kernels=use_kernels,
        )).lower(
            _spec((q, D), jnp.float32, s), _spec((q, W_SKETCH), jnp.uint32, s),
            _spec((q, ROW1.k2), jnp.int32, s),
            _spec((q, ROW1.k2), jnp.int32, s),
            _spec((n,), jnp.int32, s), _spec((n_dir, key_w), jnp.uint32, s),
            _spec((D,), jnp.float32, s), _spec((D,), jnp.float32, s),
            _spec((D,), jnp.int32, s), _spec((D,), jnp.bool_, s),
            _spec((n,), jnp.int32, s),
            _spec((n * 16 // 128, 128), jnp.uint32, s),
        )
    return lowered.compile().as_text()


def test_xla_slot_select_relays_rows_out(one_chip):
    """Why the HBM route keeps its slots in a Pallas kernel: XLA's own
    select of the slot (the XLA route) copies the gathered rows, one
    128-lane row per candidate, into another layout."""
    text = _lower_stage1(one_chip, False, 2 * N)
    copies = re.findall(r"= (\S+) copy\(", text)
    assert [c for c in copies
            if _elements(c) >= QUERY_CHUNK * ROW1.k1 * 128], copies


def test_stage1_gathers_whole_rows_past_vmem(one_chip):
    """A table too large for VMEM (2^21 rows, 128 MiB) takes the HBM
    route: XLA gathers one whole 128-lane row of the row-major table per
    candidate, and the ``sketch_slots`` kernel reads the gathered rows with
    no relayout (XLA's own slot select relays them out)."""
    q, n = QUERY_CHUNK, 2 * N
    text = _lower_stage1(one_chip, True, n)
    gathers = _assert_no_point_minor_read(text, q * ROW1.k1 * 128)
    rows = [op for op, sizes in gathers if sizes == "1,128"]
    assert rows
    for op in rows:
        assert _hlo_shape(text, op).startswith(
            f"u32[{n * 16 // 128},128]{{1,0"), _hlo_shape(text, op)
    assert _kernel_signature(text, "sketch_slots")[0] == "u32"


def test_vmem_route_follows_the_chip_and_the_reads(one_chip):
    """The table is read in VMEM only where it fits the chip's VMEM beside
    the kernel's blocks and its copy costs less than the HBM route's extra
    time per row; the largest table admitted compiles for the v5e."""
    table = N * 16 // 128 * 128 * 4             # Table-1 table, 64 MiB
    assert not hamming_ops.table_in_vmem(table, QUERY_CHUNK * 1536, 128)
    with _traced_for(one_chip):
        vmem = pltpu.get_tpu_info().vmem_capacity_bytes
        assert hamming_ops.table_in_vmem(table, QUERY_CHUNK * 1536, 128)
        assert not hamming_ops.table_in_vmem(2 * table, QUERY_CHUNK * 1536,
                                             128)
        # too few reads to pay for the copy: on the chip the HBM route
        # was the faster at 8 queries of 1,420 candidates, VMEM at 64
        assert not hamming_ops.table_in_vmem(table, 64, 128)
        assert not hamming_ops.table_in_vmem(table, 8 * 1536, 128)
        assert hamming_ops.table_in_vmem(table, 16 * 1536, 128)
        rows = (vmem - sketch_table_vmem_bytes(0, 128)) // 512
        assert hamming_ops.table_in_vmem(rows * 512, QUERY_CHUNK * 1536, 128)
        assert not hamming_ops.table_in_vmem((rows + 1) * 512,
                                             QUERY_CHUNK * 1536, 128)
    _compile(
        lambda r, sl, t: sketch_table_kernel(r, sl, t, w=W_SKETCH, s=16,
                                             bk=512),
        _spec((8, 1536), jnp.int32, one_chip),
        _spec((8, 1536), jnp.int32, one_chip),
        _spec((rows, 128), jnp.uint32, one_chip),
    )


def test_fused_search_chunk_xla_route_fits_v5e(one_chip):
    text, _ = _compile_search_chunk(one_chip, False)
    assert "tpu_custom_call" not in text


def test_hilbert_keys_compile_for_v5e_without_window_scans(one_chip):
    """The build's key pass at 2^20 × 384: a TPU lowers cumsum/cummax as a
    full-width reduce_window, which made the forest build run for hours."""
    fcfg = pubmed23.FOREST
    compiled = jax.jit(
        lambda p, lo, hi, perm, flip: hilbert.hilbert_keys(
            p, bits=fcfg.bits, key_bits=fcfg.key_bits, lo=lo, hi=hi,
            perm=perm, flip=flip)
    ).lower(
        _spec((N, D), jnp.float32, one_chip),
        _spec((D,), jnp.float32, one_chip), _spec((D,), jnp.float32, one_chip),
        _spec((D,), jnp.int32, one_chip), _spec((D,), jnp.bool_, one_chip),
    ).compile()
    assert "reduce-window" not in compiled.as_text()


# -- stage names: metadata only ------------------------------------------------

STAGE_SCOPES = {
    "chunk": ("stage1.candidates", "stage1.sketch_gather", "stage1.hamming",
              "stage1.merge", "stage2.windows", "stage2.adc", "stage2.rank"),
    "merge_order": ("merge_order.candidates", "merge_order.hamming",
                    "merge_order.merge"),
}
_KERNEL_LINE = re.compile(
    r'^\s*(?:ROOT )?%([\w.\-]+) = .*custom_call_target="tpu_custom_call"')


def _kernel_text(body_b64: str) -> str:
    """A Mosaic kernel's module as text, without its source locations and
    with its symbol (the kernel's ``name=``) replaced."""
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    with ir.Context() as ctx, ir.Location.unknown():
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        text = ir.Module.parse(base64.b64decode(body_b64)).operation.get_asm(
            enable_debug_info=False)
    return re.sub(r"^module @\S+", "module @kernel", text)


def _strip_names(hlo: str) -> str:
    """Optimized HLO text without op metadata, the stack-frame tables that
    metadata points into, and the kernels' names: the instruction name a
    ``pallas_call(name=)`` gives and the symbol inside the kernel body."""
    lines, skip = [], False
    for line in hlo.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skip = True
        elif skip:
            skip = bool(line.strip())
        else:
            lines.append(re.sub(r", metadata=\{[^}]*\}", "", line))
    text = "\n".join(lines)
    kernels = [m.group(1) for m in map(_KERNEL_LINE.match, lines) if m]
    for i, name in enumerate(kernels):
        text = re.sub(r"%" + re.escape(name) + r"(?![\w.\-])",
                      f"%kernel{i}", text)
    return re.sub(r'"body":"([^"]*)"',
                  lambda m: json.dumps({"kernel": _kernel_text(m.group(1))}),
                  text)


def _compile_small(sharding, which):
    """The fused chunk (Pallas route) or ``merge_order`` at a small shape
    of the paper's widths; the optimized HLO text."""
    n, s = 4096, sharding
    if which == "chunk":
        return _lower_search_chunk(
            s, True, n=n, trees=4, q=64,
            row=SimpleNamespace(k1=64, k2=32, h=2, k=10),
        ).compile().as_text()
    from repro.core import knn_graph

    return jax.jit(lambda a, b, o, r, sk: knn_graph.merge_order(
        a, b, o, r, sk, k1=96, k2=60)).lower(
        _spec((n, 60), jnp.int32, s), _spec((n, 60), jnp.int32, s),
        _spec((n,), jnp.int32, s), _spec((n,), jnp.int32, s),
        _spec((n, W_SKETCH), jnp.uint32, s)).compile().as_text()


@pytest.mark.parametrize("which", sorted(STAGE_SCOPES))
def test_stage_names_change_only_metadata(one_chip, monkeypatch, which):
    """Each stage scope reaches the optimized program's ``op_name``
    metadata, and the program with its names stripped is the one compiled
    with no scopes and no kernel names at all."""
    from jax.experimental import pallas as pl

    from repro.kernels import platform

    def scoped(text, scope):
        return re.search(r'op_name="[^"]*/' + re.escape(scope) + "/", text)

    jax.clear_caches()
    named = _compile_small(one_chip, which)
    for scope in STAGE_SCOPES[which]:
        assert scoped(named, scope), scope
    if which == "chunk":
        assert "%hamming_rows" in named and "%qdist_packed_windows" in named

    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    monkeypatch.setattr(platform, "pl", SimpleNamespace(
        pallas_call=lambda kernel, name=None, **kw: pl.pallas_call(
            kernel, **kw)))
    jax.clear_caches()
    plain = _compile_small(one_chip, which)
    jax.clear_caches()
    assert not any(scoped(plain, scope) for scope in STAGE_SCOPES[which])
    assert _strip_names(named) == _strip_names(plain)
