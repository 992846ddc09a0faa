"""Distributed Hilbert sort + k-NN-graph construction (1000+-node posture).

At cluster scale a single-host Hilbert sort is impossible; we implement the
paper's ordering as a **sample sort** over the mesh's 'data' axis inside
``shard_map``:

  local key-gen → local sort → all-gather splitter samples → bucket →
  ``all_to_all`` exchange (keys travel WITH their payload: global ids +
  sketches, so stage-2 filtering needs no cross-shard gathers) →
  local merge.

Every shard ends with a *padded* slice of the global Hilbert order (valid
prefix + sentinel tail; sample-sort imbalance is bounded by the oversample
rate, and overflow — dropped points — is returned as a counter that MUST be
zero in production, asserted in tests).

Task-2 neighbor windows cross shard boundaries via a ±k₁ **halo exchange**
(``lax.ppermute`` of each shard's valid edge rows), making the paper's
"extract k₁ neighbors around position i" boundary-correct at any device
count.  Candidates are routed back to their home shard (gid // local_n)
with a second all_to_all, where the running sketch-filtered top-k₂ merge is
the same associative merge the single-device path uses.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import hilbert, quantize, sketch
from repro.core.types import ForestConfig, GraphParams

_MAXU = jnp.uint32(0xFFFFFFFF)


# ---------------------------------------------------------------------------
# Sample sort (shard_map core)
# ---------------------------------------------------------------------------


def _bucket_of(splitters: jax.Array, keys_sorted: jax.Array) -> jax.Array:
    """splitters (p-1, W); sorted keys (n, W) -> bucket ids in [0, p)."""
    n = keys_sorted.shape[0]
    m = splitters.shape[0]
    steps = max(1, int(np.ceil(np.log2(m + 1))))

    def body(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // 2
        midk = splitters[mid]
        go_right = ~hilbert.lex_less(keys_sorted, midk)  # key >= splitter
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right, hi, mid)
        return lo, hi

    lo = jnp.zeros((n,), jnp.int32)
    hi = jnp.full((n,), m, jnp.int32)
    lo, _ = lax.fori_loop(0, steps, body, (lo, hi))
    return lo


def sample_sort_sharded(
    keys: jax.Array,              # (N, W) uint32, sharded over axis dim 0
    payload: Dict[str, jax.Array],  # each (N, ...), same sharding
    mesh: Mesh,
    axis: str = "data",
    oversample: int = 64,
    cap_factor: float = 2.0,
):
    """Returns (keys_out (N·cf? padded per shard), payload_out, n_valid, overflow).

    Output arrays have per-shard length ``cap_total = cap_factor · N/p``;
    rows ≥ n_valid[shard] are sentinels.  Concatenating the valid prefixes
    over shards yields the globally sorted sequence.
    """
    p = mesh.shape[axis]
    n, w = keys.shape
    local_n = n // p
    cap = max(8, int(cap_factor * local_n / p))  # per (src,dst) bucket slots
    cap_total = cap * p

    def shard_fn(keys_l, *payload_l):
        names = list(payload.keys())
        payload_d = dict(zip(names, payload_l))
        ln = keys_l.shape[0]

        order = hilbert.lexsort_words(keys_l)
        keys_s = keys_l[order]
        pay_s = {k: v[order] for k, v in payload_d.items()}

        # --- splitters from an all-gathered sample ---
        s = min(oversample, ln)
        samp_idx = (jnp.arange(s) * (ln // s)).astype(jnp.int32)
        cand = keys_s[samp_idx]                       # (s, W)
        allc = lax.all_gather(cand, axis)             # (p, s, W)
        flat = allc.reshape(p * s, w)
        flat = flat[hilbert.lexsort_words(flat)]
        split_idx = (jnp.arange(1, p) * s).astype(jnp.int32)
        splitters = flat[split_idx - 1]               # (p-1, W)

        bucket = _bucket_of(splitters, keys_s)        # (ln,) nondecreasing
        counts = jnp.sum(jax.nn.one_hot(bucket, p, dtype=jnp.int32), axis=0)
        offsets = jnp.cumsum(counts) - counts
        pos = jnp.arange(ln, dtype=jnp.int32) - offsets[bucket]
        valid = pos < cap
        overflow = jnp.sum(~valid).astype(jnp.int32)
        slot = jnp.where(valid, bucket * cap + pos, p * cap)

        send_keys = jnp.full((p * cap + 1, w), _MAXU, jnp.uint32)
        send_keys = send_keys.at[slot].set(keys_s)[: p * cap]
        recv_keys = lax.all_to_all(
            send_keys.reshape(p, cap, w), axis, split_axis=0, concat_axis=0,
            tiled=False,
        ).reshape(p * cap, w)

        recv_pay = {}
        for kname, v in pay_s.items():
            fill = (
                jnp.zeros((p * cap + 1,) + v.shape[1:], v.dtype)
                if jnp.issubdtype(v.dtype, jnp.floating)
                else jnp.full((p * cap + 1,) + v.shape[1:], -1, v.dtype)
            )
            sv = fill.at[slot].set(v)[: p * cap]
            rv = lax.all_to_all(
                sv.reshape((p, cap) + v.shape[1:]), axis, split_axis=0,
                concat_axis=0, tiled=False,
            ).reshape((p * cap,) + v.shape[1:])
            recv_pay[kname] = rv

        # --- local merge; sentinels (MAXU keys) sort to the tail ---
        morder = hilbert.lexsort_words(recv_keys)
        keys_o = recv_keys[morder]
        pay_o = {k: v[morder] for k, v in recv_pay.items()}
        is_valid = ~jnp.all(keys_o == _MAXU, axis=1)
        n_valid = jnp.sum(is_valid).astype(jnp.int32)
        out = [keys_o] + [pay_o[k] for k in names]
        return (*out, n_valid[None], overflow[None])

    in_specs = (P(axis),) + tuple(P(axis) for _ in payload)
    out_specs = (
        (P(axis),) + tuple(P(axis) for _ in payload) + (P(axis), P(axis))
    )
    fn = shard_map(shard_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                   check_vma=False)
    outs = fn(keys, *payload.values())
    keys_out = outs[0]
    pay_out = dict(zip(payload.keys(), outs[1 : 1 + len(payload)]))
    n_valid = outs[-2]
    overflow = outs[-1]
    return keys_out, pay_out, n_valid, overflow


# ---------------------------------------------------------------------------
# Distributed Hilbert order
# ---------------------------------------------------------------------------


def distributed_hilbert_order(
    points: jax.Array,            # (N, d) sharded over 'data'
    mesh: Mesh,
    cfg: ForestConfig,
    lo: jax.Array,
    hi: jax.Array,
    perm: Optional[jax.Array] = None,
    flip: Optional[jax.Array] = None,
    payload: Optional[Dict[str, jax.Array]] = None,
    axis: str = "data",
    cap_factor: float = 2.0,
):
    """Global Hilbert ordering of sharded points (+payload), sample-sorted."""
    n = points.shape[0]
    # Each device keys its own rows: the key pass maps over row blocks,
    # which on the global array would gather every block to every device.
    keys = shard_map(
        lambda p: hilbert.hilbert_keys(
            p, bits=cfg.bits, key_bits=cfg.key_bits, lo=lo, hi=hi,
            perm=perm, flip=flip),
        mesh=mesh, in_specs=P(axis, None), out_specs=P(axis, None),
    )(points)
    gids = jnp.arange(n, dtype=jnp.int32)
    pay = {"gid": gids}
    if payload:
        pay.update(payload)
    return sample_sort_sharded(keys, pay, mesh, axis=axis, cap_factor=cap_factor)


def hilbert_partition(
    points: jax.Array,            # (n, d) host or device array
    cfg: ForestConfig,
    mesh: Optional[Mesh] = None,
    n_shards: Optional[int] = None,
    axis: str = "data",
) -> list:
    """Row-partition ``points`` into contiguous runs of the master Hilbert order.

    The layout primitive of :class:`repro.index.sharded.ShardedHilbertIndex`:
    each returned ``np.ndarray`` of global row ids is one shard's residency
    set, and concatenating them walks the (un-permuted) master Hilbert curve
    — so every shard's rows are a locality-tight curve segment and a
    per-shard top-k merge loses as little recall as the curve allows
    (the hyperorthogonal well-folded ordering argument).

    Multi-device meshes compute the order with the sample sort above
    (each device keys+sorts only its slice); when the mesh is trivial or
    ``n`` is not divisible by the device count (the sample sort's shard_map
    needs equal input slices) it falls back to the single-device sort —
    same keys, same order up to equal-key ties.

    Returns ``n_shards`` id arrays of length ``ceil(n / n_shards)`` (the
    last may be shorter; shards past the data are empty arrays).
    """
    from repro.launch.mesh import data_mesh

    if mesh is None:
        mesh = data_mesh()
    p = mesh.shape[axis]
    if n_shards is None:
        n_shards = p
    n = points.shape[0]
    # ``points`` may be a host array: bounds are exact either way, and the
    # sample sort then uploads each device's slice straight to its device.
    lo = jnp.asarray(np.min(points, axis=0))
    hi = jnp.asarray(np.max(points, axis=0))
    order = None
    if p > 1 and n % p == 0:
        pts_sh = jax.device_put(points, NamedSharding(mesh, P(axis, None)))
        keys_o, pay_o, n_valid, ovf = distributed_hilbert_order(
            pts_sh, mesh, cfg, lo, hi, axis=axis
        )
        if int(jnp.sum(ovf)) == 0:
            nv = np.asarray(n_valid)
            gids = np.asarray(pay_o["gid"]).reshape(p, -1)
            order = np.concatenate([gids[r, : nv[r]] for r in range(p)])
        # overflow (bounded-capacity bucket spill) would drop rows; fall
        # back to the exact single-device sort rather than lose points.
    if order is None:
        from repro.core.search import hilbert_master_sort

        order, _ = hilbert_master_sort(jnp.asarray(points), cfg, lo, hi)
        order = np.asarray(order)
    per = -(-n // n_shards)
    return [order[s * per : (s + 1) * per] for s in range(n_shards)]


# ---------------------------------------------------------------------------
# Partition bounds + insert routing (the sharded-mutable write path)
# ---------------------------------------------------------------------------


_MAX_KEY_FILL = np.uint32(0xFFFFFFFF)


def _np_lex_ge(keys: np.ndarray, bound: np.ndarray) -> np.ndarray:
    """Vectorized lexicographic ``keys[i] >= bound`` over (m, W) uint32 rows."""
    m = keys.shape[0]
    result = np.zeros((m,), np.bool_)
    decided = np.zeros((m,), np.bool_)
    for w in range(keys.shape[1]):
        gt = ~decided & (keys[:, w] > bound[w])
        lt = ~decided & (keys[:, w] < bound[w])
        result |= gt
        decided |= gt | lt
    result |= ~decided  # all words equal -> key == bound -> ge
    return result


def curve_partition_bounds(
    first_points: list,            # per shard: (d,) np array or None (empty)
    cfg: ForestConfig,
    lo: np.ndarray,
    hi: np.ndarray,
) -> np.ndarray:
    """Master-curve boundary keys of a contiguous Hilbert partition.

    ``first_points[s]`` is the first row (in master-curve order) that shard
    ``s`` owns, or ``None`` for an empty shard.  Returns ``(S-1, W)`` uint32
    where row ``s-1`` is shard ``s``'s opening key; empty shards get the
    all-ones MAX key so :func:`route_to_shards` never routes new rows to
    them (the curve ran out of data before reaching their range).  Keys are
    computed with the *global* ``lo``/``hi`` bounds the partition itself
    used, so routing agrees with :func:`hilbert_partition` up to equal-key
    ties.
    """
    from repro.core import hilbert as hilbert_lib

    n_shards = len(first_points)
    w = hilbert_lib.key_words(cfg.key_bits)
    bounds = np.full((max(n_shards - 1, 0), w), _MAX_KEY_FILL, np.uint32)
    present = [s for s in range(1, n_shards) if first_points[s] is not None]
    if present:
        pts = jnp.asarray(np.stack([first_points[s] for s in present]))
        keys = np.asarray(hilbert_lib.hilbert_keys(
            pts, bits=cfg.bits, key_bits=cfg.key_bits,
            lo=jnp.asarray(lo), hi=jnp.asarray(hi),
        ))
        for row, s in enumerate(present):
            bounds[s - 1] = keys[row]
    return bounds


def route_to_shards(
    points: np.ndarray,            # (m, d)
    cfg: ForestConfig,
    lo: np.ndarray,
    hi: np.ndarray,
    bounds: np.ndarray,            # (S-1, W) from curve_partition_bounds
) -> np.ndarray:
    """Route rows to the shard owning their master-curve range.

    Returns ``(m,)`` int32 shard indices: ``sum_s [key >= bounds[s]]`` — a
    lexicographic searchsorted against the partition's opening keys.  Points
    outside the frozen ``lo``/``hi`` box clamp to the box edge (same
    behavior as the curve quantization itself), so routing is total.
    """
    from repro.core import hilbert as hilbert_lib

    if points.shape[0] == 0:
        return np.zeros((0,), np.int32)
    keys = np.asarray(hilbert_lib.hilbert_keys(
        jnp.asarray(points, jnp.float32), bits=cfg.bits,
        key_bits=cfg.key_bits, lo=jnp.asarray(lo), hi=jnp.asarray(hi),
    ))
    shard = np.zeros((points.shape[0],), np.int32)
    for b in bounds:
        shard += _np_lex_ge(keys, b).astype(np.int32)
    return shard


# ---------------------------------------------------------------------------
# Halo windows (Task-2 stage 1, boundary-correct)
# ---------------------------------------------------------------------------


def halo_window_candidates(
    gids_sorted: jax.Array,       # (N_pad,) int32 sharded; -1 = sentinel
    sketches_sorted: jax.Array,   # (N_pad, Ws) uint32 sharded (same order)
    n_valid: jax.Array,           # (p,) int32 sharded (1 per shard)
    mesh: Mesh,
    k1: int,
    axis: str = "data",
):
    """Per resident point: (k1 candidate gids, k1 hamming dists), windows
    crossing shard edges via ppermute halo of each shard's valid edges."""
    p = mesh.shape[axis]
    half = k1 // 2

    def shard_fn(gids_l, sk_l, nv):
        ln = gids_l.shape[0]
        nv = nv[0]
        rank = lax.axis_index(axis)

        # halo: send my first/last `half` VALID rows to prev/next shard
        first_g = lax.dynamic_slice_in_dim(gids_l, 0, half)
        first_s = lax.dynamic_slice_in_dim(sk_l, 0, half)
        start = jnp.maximum(nv - half, 0)
        last_g = jnp.take(gids_l, start + jnp.arange(half), axis=0,
                          mode="clip")
        last_s = jnp.take(sk_l, start + jnp.arange(half), axis=0, mode="clip")
        # mask tail halo rows beyond nv
        tail_valid = (start + jnp.arange(half)) < nv
        last_g = jnp.where(tail_valid, last_g, -1)

        fwd = [(i, (i + 1) % p) for i in range(p)]
        bwd = [(i, (i - 1) % p) for i in range(p)]
        from_prev_g = lax.ppermute(last_g, axis, fwd)    # prev shard's tail
        from_prev_s = lax.ppermute(last_s, axis, fwd)
        from_next_g = lax.ppermute(first_g, axis, bwd)   # next shard's head
        from_next_s = lax.ppermute(first_s, axis, bwd)
        # ring wrap: rank 0 has no prev, rank p-1 no next
        from_prev_g = jnp.where(rank == 0, -1, from_prev_g)
        from_next_g = jnp.where(rank == p - 1, -1, from_next_g)

        # ext layout: [prev-halo | local rows | half sentinel slots]; the
        # next-shard halo is spliced in right AFTER the valid prefix (at
        # ext index half+nv) so windows at the boundary see true neighbors,
        # not sentinel padding.
        ext_g = jnp.concatenate(
            [from_prev_g, gids_l, jnp.full((half,), -1, gids_l.dtype)]
        )
        ext_s = jnp.concatenate(
            [from_prev_s, sk_l, jnp.zeros((half,) + sk_l.shape[1:], sk_l.dtype)]
        )
        ext_g = lax.dynamic_update_slice_in_dim(ext_g, from_next_g, half + nv, 0)
        ext_s = lax.dynamic_update_slice_in_dim(ext_s, from_next_s, half + nv, 0)
        # resident row j lives at ext position j + half; window is
        # [j+half-half, j+half+half] minus self.
        deltas = jnp.concatenate([
            jnp.arange(-half, 0, dtype=jnp.int32),
            jnp.arange(1, k1 - half + 1, dtype=jnp.int32),
        ])
        pos = jnp.arange(ln, dtype=jnp.int32)[:, None] + half + deltas[None, :]
        pos = jnp.clip(pos, 0, ln + 2 * half - 1)
        cand_g = jnp.take(ext_g, pos, axis=0, mode="clip")      # (ln, k1)
        cand_s = jnp.take(ext_s, pos, axis=0, mode="clip")      # (ln, k1, Ws)
        # candidates beyond this shard's valid region point at sentinel rows
        row_ok = (jnp.arange(ln, dtype=jnp.int32) < nv)[:, None]
        cand_g = jnp.where(row_ok & (cand_g >= 0), cand_g, -1)

        hd = sketch.hamming_distance(sk_l[:, None, :], cand_s)   # (ln, k1)
        hd = jnp.where(cand_g >= 0, hd, jnp.int32(2**30))
        self_mask = cand_g == gids_l[:, None]
        hd = jnp.where(self_mask, jnp.int32(2**30), hd)
        return cand_g, hd

    fn = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis)),
        check_vma=False,
    )
    return fn(gids_sorted, sketches_sorted, n_valid)


# ---------------------------------------------------------------------------
# Route results home + merge (Task-2 main loop)
# ---------------------------------------------------------------------------


def route_home(
    owner_gid: jax.Array,   # (N_pad,) sharded; -1 sentinel
    cand_g: jax.Array,      # (N_pad, k1)
    cand_d: jax.Array,      # (N_pad, k1)
    mesh: Mesh,
    n_points: int,
    axis: str = "data",
    cap_factor: float = 1.5,
):
    """all_to_all candidates to gid's home shard; returns them in home-local
    gid order: (cands (local_n, k1), dists (local_n, k1)) per shard."""
    p = mesh.shape[axis]
    local_n = n_points // p
    k1 = cand_g.shape[1]
    cap = max(8, int(cap_factor * local_n / p))

    def shard_fn(og, cg, cd):
        ln = og.shape[0]
        home = jnp.where(og >= 0, og // local_n, p)      # (ln,)
        # positions within each destination bucket
        onehot = jax.nn.one_hot(jnp.clip(home, 0, p - 1), p, dtype=jnp.int32)
        onehot = jnp.where((og >= 0)[:, None], onehot, 0)
        run = jnp.cumsum(onehot, axis=0) - onehot
        pos = jnp.sum(run * onehot, axis=1)
        valid = (og >= 0) & (pos < cap)
        overflow = jnp.sum((og >= 0) & (pos >= cap)).astype(jnp.int32)
        slot = jnp.where(valid, home * cap + pos, p * cap)

        sg = jnp.full((p * cap + 1,), -1, jnp.int32).at[slot].set(og)[: p * cap]
        scg = jnp.full((p * cap + 1, k1), -1, jnp.int32).at[slot].set(cg)[: p * cap]
        scd = jnp.full((p * cap + 1, k1), 2**30, jnp.int32).at[slot].set(cd)[: p * cap]

        rg = lax.all_to_all(sg.reshape(p, cap), axis, 0, 0, tiled=False).reshape(-1)
        rcg = lax.all_to_all(scg.reshape(p, cap, k1), axis, 0, 0, tiled=False).reshape(-1, k1)
        rcd = lax.all_to_all(scd.reshape(p, cap, k1), axis, 0, 0, tiled=False).reshape(-1, k1)

        # scatter into local gid order
        rank = lax.axis_index(axis)
        local_gid = jnp.where(rg >= 0, rg - rank * local_n, local_n)
        out_c = jnp.full((local_n + 1, k1), -1, jnp.int32).at[local_gid].set(rcg)[:local_n]
        out_d = jnp.full((local_n + 1, k1), 2**30, jnp.int32).at[local_gid].set(rcd)[:local_n]
        return out_c, out_d, overflow[None]

    fn = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis)),
        check_vma=False,
    )
    return fn(owner_gid, cand_g, cand_d)


def distributed_knn_graph(
    points: jax.Array,            # (N, d) — device_put sharded over 'data'
    params: GraphParams,
    forest_cfg: ForestConfig,
    mesh: Mesh,
    axis: str = "data",
) -> Tuple[jax.Array, jax.Array, int]:
    """Multi-node Task 2.  Returns (ids (N,k), d² (N,k), total_overflow).

    Quantized codes are REPLICATED for the final ADC ranking (the paper's
    4-bit codes: 23M×384 = 4.4 GB — replicable at any scale); vectors,
    sketches and all sort traffic stay sharded.
    """
    n, d = points.shape
    quant = quantize.fit(points, bits=4)
    codes = quantize.encode(quant, points)
    sks = sketch.sketches_from_codes(codes, bits=4)
    lo = jnp.min(points, axis=0)
    hi = jnp.max(points, axis=0)

    data_sh = NamedSharding(mesh, P(axis))
    points = jax.device_put(points, NamedSharding(mesh, P(axis, None)))
    sks = jax.device_put(sks, NamedSharding(mesh, P(axis, None)))

    rng = np.random.default_rng(params.seed)
    best_id = jax.device_put(
        jnp.full((n, params.k2), -1, jnp.int32), NamedSharding(mesh, P(axis, None))
    )
    best_d = jax.device_put(
        jnp.full((n, params.k2), 2**30, jnp.int32), NamedSharding(mesh, P(axis, None))
    )
    total_overflow = 0
    for _ in range(params.n_orders):
        perm = jnp.asarray(rng.permutation(d).astype(np.int32))
        flip = jnp.asarray(rng.integers(0, 2, d).astype(bool))
        keys_o, pay_o, n_valid, ovf1 = distributed_hilbert_order(
            points, mesh, forest_cfg, lo, hi, perm, flip,
            payload={"sk": sks}, axis=axis,
        )
        cand_g, cand_d = halo_window_candidates(
            pay_o["gid"], pay_o["sk"], n_valid, mesh, params.k1, axis=axis
        )
        home_c, home_d, ovf2 = route_home(
            pay_o["gid"], cand_g, cand_d, mesh, n, axis=axis
        )
        best_id, best_d = _merge_sharded(best_id, best_d, home_c, home_d, params.k2)
        total_overflow += int(jnp.sum(ovf1)) + int(jnp.sum(ovf2))

    # final: exact ADC ranking against replicated codes
    ids, dists = _final_adc(points, best_id, quant, codes, params.k)
    return ids, dists, total_overflow


@functools.partial(jax.jit, static_argnames=("k2",))
def _merge_sharded(best_id, best_d, new_id, new_d, k2: int):
    ids = jnp.concatenate([best_id, new_id], axis=1)
    ds = jnp.concatenate([best_d, new_d], axis=1)
    sort_idx = jnp.argsort(ids, axis=1)
    ids_s = jnp.take_along_axis(ids, sort_idx, axis=1)
    ds_s = jnp.take_along_axis(ds, sort_idx, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros_like(ids_s[:, :1], bool), ids_s[:, 1:] == ids_s[:, :-1]], axis=1
    )
    ds_s = jnp.where(dup | (ids_s < 0), jnp.int32(2**30), ds_s)
    neg, idx = lax.top_k(-ds_s, k2)
    return jnp.take_along_axis(ids_s, idx, axis=1), -neg


@functools.partial(jax.jit, static_argnames=("k",))
def _final_adc(points, best_id, quant, codes, k: int):
    cand_codes = jnp.take(codes, jnp.maximum(best_id, 0), axis=0)  # (N,k2,d)
    d2 = quantize.adc_distance(quant, points, cand_codes)
    n = points.shape[0]
    d2 = jnp.where(best_id < 0, jnp.inf, d2)
    d2 = jnp.where(best_id == jnp.arange(n, dtype=jnp.int32)[:, None], jnp.inf, d2)
    neg, idx = lax.top_k(-d2, k)
    return jnp.take_along_axis(best_id, idx, axis=1), -neg


# ---------------------------------------------------------------------------
# Cross-shard top-k merge (the sharded facades' reduction tail)
# ---------------------------------------------------------------------------


def is_pow2(n: int) -> bool:
    return n >= 1 and (n & (n - 1)) == 0


def resolve_merge(merge: str, n_shards: int) -> str:
    """Resolve a ``merge`` knob ("auto" | "gather" | "tree") to a concrete path.

    ``"auto"`` picks the butterfly tree when the shard count is a power of
    two (its XOR-partner schedule needs one) and the flat gather otherwise;
    an *explicit* ``"tree"`` on a non-pow2 shard count is a caller error and
    raises rather than silently degrading.
    """
    if merge not in ("auto", "gather", "tree"):
        raise ValueError(f"merge={merge!r}: expected 'auto', 'gather' or 'tree'")
    if merge == "auto":
        return "tree" if is_pow2(n_shards) else "gather"
    if merge == "tree" and not is_pow2(n_shards):
        raise ValueError(
            f"merge='tree' needs a power-of-two shard count, got {n_shards}; "
            "use merge='auto' to fall back to 'gather'"
        )
    return merge


def tree_merge_topk(ids, d2, *, k: int, axis: str, axis_size: int,
                    prune: bool = False):
    """Butterfly all-reduce of :func:`repro.core.search.merge_topk`.

    Runs INSIDE a shard_map body.  Each rank first deflates its local
    candidate pool (Q, C) — however inflated by padding/tombstone slack —
    to a true local top-k, then performs log2(S) ``lax.ppermute`` hops on
    the XOR-partner (recursive-doubling) schedule: at step ``s`` rank
    ``r`` exchanges its running (Q, k) partial with rank ``r ^ s`` and
    merges.  Interconnect traffic is k rows per query per hop instead of
    the gather path's (S-1)·C rows, and the flat merge over an S·C pool
    is replaced by log2(S) merges over 2k pools.

    Determinism: both members of a pair merge the SAME concatenation —
    the lower rank's block first (``merge_topk_pair`` keyed on
    ``(rank & s) == 0``) — so by induction every rank holds bit-identical
    partials after every hop, and the final (Q, k) is safe to declare
    replicated (``out_specs P(None)``) even with ``check_vma=False``.

    ``prune=True`` adds one ``lax.pmin`` of each rank's local kth-best
    distance before the first hop and masks local candidates strictly
    worse than that global bound λ.  Exact: some rank holds k distinct
    ids at distance ≤ λ, so a candidate with d > λ can never enter the
    global top-k, and survivors' tie order is untouched — results stay
    bit-equal, ids included.

    Requires ``axis_size`` to be a power of two (checked by
    :func:`resolve_merge` before tracing).
    """
    from repro.core import search as search_lib

    ids_k, d_k = search_lib.merge_topk(ids, d2, k=k)  # shard-local deflation
    if axis_size == 1:
        return ids_k, d_k
    rank = lax.axis_index(axis)
    if prune:
        lam = lax.pmin(d_k[:, -1], axis)
        keep = d_k <= lam[:, None]
        ids_k = jnp.where(keep, ids_k, -1)
        d_k = jnp.where(keep, d_k, jnp.inf)
    step = 1
    while step < axis_size:
        perm = [(r, r ^ step) for r in range(axis_size)]
        other_ids = lax.ppermute(ids_k, axis, perm)
        other_d = lax.ppermute(d_k, axis, perm)
        first = (rank & step) == 0
        ids_k, d_k = search_lib.merge_topk_pair(
            ids_k, d_k, other_ids, other_d, first, k=k
        )
        step *= 2
    return ids_k, d_k


def gather_merge_topk(ids, d2, *, k: int, axis: str):
    """Flat reference reduction: all_gather every rank's pool, merge once.

    The pre-tree behavior, kept bit-exact as ``merge="gather"`` — the
    parity baseline the tree path is asserted against in
    ``scripts/sharded_check.py``.  Per device it moves (S-1)·C candidate
    rows per query and flat-merges an S·C pool.
    """
    from repro.core import search as search_lib

    all_ids = lax.all_gather(ids, axis)  # (S, Q, C)
    all_d = lax.all_gather(d2, axis)
    qn = ids.shape[0]
    pool = all_ids.shape[0] * all_ids.shape[2]
    merged_ids = jnp.moveaxis(all_ids, 0, 1).reshape(qn, pool)
    merged_d = jnp.moveaxis(all_d, 0, 1).reshape(qn, pool)
    return search_lib.merge_topk(merged_ids, merged_d, k=k)


def cross_shard_merge_topk(ids, d2, *, k: int, axis: str, axis_size: int,
                           merge: str, prune: bool = False):
    """The one cross-shard merge tail shared by both sharded facades.

    Called inside the shard_map body with each rank's (Q, C) local
    candidates (global ids, -1 padding, +inf masked distances); returns a
    replicated (Q, k).  ``merge`` must already be resolved to ``"gather"``
    or ``"tree"`` (see :func:`resolve_merge`); the two return identical
    sorted distances bit-for-bit, and identical ids up to distance ties
    (with ``prune``, ids are bit-equal to the unpruned tree).
    """
    if merge == "gather":
        return gather_merge_topk(ids, d2, k=k, axis=axis)
    if merge == "tree":
        return tree_merge_topk(ids, d2, k=k, axis=axis, axis_size=axis_size,
                               prune=prune)
    raise ValueError(f"unresolved merge strategy {merge!r}")
