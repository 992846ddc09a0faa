"""Top-k MoE with sort-based dispatch, expert-parallel over the model axis.

TPU adaptation notes (DESIGN.md §Arch): GShard's dense one-hot dispatch
einsum is O(T·E·C·D) — prohibitive.  We dispatch with a per-batch-row
argsort: the sort axis (S·k) is unsharded, so under GSPMD every device sorts
its local rows with **zero collectives**.  Expert weights and the dispatch
buffer shard over 'model' (EP == TP on the expert axis); the combine gather
re-shards expert outputs back to token order (an all-gather of cf·k× the
activation bytes over 'model' — visible in the collective roofline and a
§Perf hillclimb lever).

Tokens beyond an expert's capacity C = cf·S·k/E are dropped (standard
GShard semantics); the router carries a switch-style load-balance aux loss.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.sharding import ShardingRules, shard

Params = Dict[str, Any]


def moe_init(cfg: ModelConfig, key: jax.Array) -> Params:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    scale = 1.0 / np.sqrt(d)
    p = {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * scale,
        "w1": jax.random.normal(ks[1], (e, d, f), jnp.float32) * scale,
        "w2": jax.random.normal(ks[2], (e, f, d), jnp.float32) / np.sqrt(f),
    }
    if cfg.act in ("silu", "gelu"):
        p["w3"] = jax.random.normal(ks[3], (e, d, f), jnp.float32) * scale
    return p


def expert_capacity(cfg: ModelConfig, seq: int) -> int:
    c = int(np.ceil(cfg.capacity_factor * seq * cfg.topk_experts / cfg.n_experts))
    return max(8, -(-c // 8) * 8)  # sublane-align


def moe_forward_ep(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,                 # (B, S, D)
    rules: ShardingRules,
) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE via shard_map: zero-collective dispatch + masked
    local combine + ONE psum(B,S,D) per layer.

    The GSPMD combine gathers the (B, E·C, D) expert-output buffer over
    'model' — cf·k ≈ 10-20× the activation bytes (granite-moe train_4k:
    85 s collective term).  Per-rank control makes each model rank gather
    only from its LOCAL experts and contribute a partial sum; the psum
    moves exactly activation-sized bytes, like a dense TP FFN.
    """
    e, k = cfg.n_experts, cfg.topk_experts
    mesh = rules.mesh
    msize = mesh.shape["model"]
    e_loc = e // msize
    cap = expert_capacity(cfg, x.shape[1])
    fsdp = rules.fsdp

    def body(xl, router, w1, w2, w3):
        # xl (B_l, S, D) — identical across model ranks; w* (E_loc, ...)
        if fsdp is not None:
            # w1/w3 are (E,D,F) sharded on D (axis 1); w2 is (E,F,D)
            # sharded on D (axis 2).
            w1 = lax.all_gather(w1, fsdp, axis=1, tiled=True)
            w2 = lax.all_gather(w2, fsdp, axis=2, tiled=True)
            if w3 is not None:
                w3 = lax.all_gather(w3, fsdp, axis=1, tiled=True)
        b, s, d = xl.shape
        t = s * k
        dtype = xl.dtype
        rank = lax.axis_index("model")
        e0 = rank * e_loc

        logits = (xl @ router.astype(dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, eidx = jax.lax.top_k(probs, k)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
        me = jnp.mean(probs, axis=(0, 1))
        ce = jnp.mean(jnp.sum(jax.nn.one_hot(eidx, e, dtype=jnp.float32),
                              axis=2), axis=(0, 1)) / k
        aux = e * jnp.sum(me * ce)

        flat_e = eidx.reshape(b, t)
        sort_i = jnp.argsort(flat_e, axis=1)
        sorted_e = jnp.take_along_axis(flat_e, sort_i, axis=1)
        counts = jnp.sum(jax.nn.one_hot(flat_e, e, dtype=jnp.int32), axis=1)
        starts = jnp.cumsum(counts, axis=1) - counts
        pos_in_e = (jnp.arange(t, dtype=jnp.int32)[None, :]
                    - jnp.take_along_axis(starts, sorted_e, axis=1))
        keep = pos_in_e < cap
        slot_sorted = jnp.where(keep, sorted_e * cap + pos_in_e, e * cap)
        slot = jnp.zeros((b, t), jnp.int32).at[
            jnp.arange(b, dtype=jnp.int32)[:, None], sort_i
        ].set(slot_sorted)

        # local expert range [e0·cap, (e0+e_loc)·cap)
        slot_loc = slot - e0 * cap
        in_range = (slot_loc >= 0) & (slot_loc < e_loc * cap)
        slot_loc = jnp.where(in_range, slot_loc, e_loc * cap)

        tok_of_flat = jnp.arange(t, dtype=jnp.int32) // k
        xk = jnp.take(xl, tok_of_flat, axis=1)                     # (B,T,D)
        buf = jnp.zeros((b, e_loc * cap + 1, d), dtype)
        buf = buf.at[jnp.arange(b, dtype=jnp.int32)[:, None], slot_loc].set(
            jnp.where(in_range[:, :, None], xk, 0))
        buf = buf[:, : e_loc * cap].reshape(b, e_loc, cap, d)

        h = jnp.einsum("becd,edf->becf", buf, w1.astype(dtype))
        if cfg.act == "silu":
            h = jax.nn.silu(h) * jnp.einsum("becd,edf->becf", buf, w3.astype(dtype))
        elif cfg.act == "gelu":
            h = jax.nn.gelu(h) * jnp.einsum("becd,edf->becf", buf, w3.astype(dtype))
        elif cfg.act == "relu2":
            r = jax.nn.relu(h)
            h = r * r
        else:
            raise ValueError(cfg.act)
        y = jnp.einsum("becf,efd->becd", h, w2.astype(dtype))

        y_flat = jnp.concatenate(
            [y.reshape(b, e_loc * cap, d), jnp.zeros((b, 1, d), dtype)], axis=1)
        gath = jnp.take_along_axis(y_flat, slot_loc[:, :, None], axis=1)
        gath = gath.reshape(b, s, k, d)
        partial = jnp.sum(gath * gate[..., None].astype(dtype), axis=2)
        out = lax.psum(partial, "model")
        return out, aux

    w3 = p.get("w3")
    wspec = P("model", fsdp, None)
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(rules.batch, None, None), P(None, None), wspec,
                  P("model", None, fsdp), (wspec if w3 is not None else P())),
        out_specs=(P(rules.batch, None, None), P()),
        check_vma=False,
    )
    out, aux = fn(x, p["router"],
                  p["w1"], p["w2"], w3 if w3 is not None else jnp.zeros(()))
    return out, aux


def moe_forward(
    cfg: ModelConfig,
    p: Params,
    x: jax.Array,                 # (B, S, D)
    rules: ShardingRules,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output (B,S,D), aux load-balance loss scalar)."""
    if (rules.mesh is not None and rules.experts == "model"
            and cfg.n_experts % rules.mesh.shape["model"] == 0):
        return moe_forward_ep(cfg, p, x, rules)
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.topk_experts
    t = s * k
    cap = expert_capacity(cfg, s)
    dtype = x.dtype

    logits = (x @ p["router"].astype(dtype)).astype(jnp.float32)  # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, k)                          # (B,S,K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # Switch aux loss: E * sum_e f_e * p_e  (f = token fraction, p = prob mass)
    me = jnp.mean(probs, axis=(0, 1))                             # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(eidx, e, dtype=jnp.float32), axis=2), axis=(0, 1)
    ) / k
    aux = e * jnp.sum(me * ce)

    # --- dispatch: per-row sort by expert id (local under batch sharding) ---
    flat_e = eidx.reshape(b, t)
    sort_i = jnp.argsort(flat_e, axis=1)                          # (B,T)
    sorted_e = jnp.take_along_axis(flat_e, sort_i, axis=1)
    counts = jnp.sum(jax.nn.one_hot(flat_e, e, dtype=jnp.int32), axis=1)  # (B,E)
    starts = jnp.cumsum(counts, axis=1) - counts                  # exclusive
    pos_in_e = (
        jnp.arange(t, dtype=jnp.int32)[None, :]
        - jnp.take_along_axis(starts, sorted_e, axis=1)
    )
    keep = pos_in_e < cap
    slot_sorted = jnp.where(keep, sorted_e * cap + pos_in_e, e * cap)  # drop -> sink
    # unsort: slot per (token, k)
    slot = jnp.zeros((b, t), jnp.int32).at[
        jnp.arange(b, dtype=jnp.int32)[:, None], sort_i
    ].set(slot_sorted)

    tok_of_flat = jnp.arange(t, dtype=jnp.int32)[None, :] // k      # (1,T)
    xk = jnp.take(x, tok_of_flat[0], axis=1)                        # (B,T,D)

    buf = jnp.zeros((b, e * cap + 1, d), dtype)
    buf = buf.at[jnp.arange(b, dtype=jnp.int32)[:, None], slot].set(xk)
    buf = buf[:, : e * cap, :].reshape(b, e, cap, d)
    buf = shard(buf, rules, "batch", "experts", "capacity", "d_model")

    # --- expert FFN (experts sharded over 'model') ---
    # (B,E,C,F): EP shards the expert axis; when E doesn't divide the
    # model axis (mixtral 8e/16) rules.experts is None and F carries the
    # model axis instead (intra-expert TP) — never both on one tensor.
    h = jnp.einsum("becd,edf->becf", buf, p["w1"].astype(dtype))
    h = shard(h, rules, "batch", "experts", "capacity",
              None if rules.experts else "mlp")
    if cfg.act == "silu":
        h = jax.nn.silu(h) * jnp.einsum("becd,edf->becf", buf, p["w3"].astype(dtype))
    elif cfg.act == "gelu":
        h = jax.nn.gelu(h) * jnp.einsum("becd,edf->becf", buf, p["w3"].astype(dtype))
    elif cfg.act == "relu2":
        r = jax.nn.relu(h)
        h = r * r
    else:
        raise ValueError(cfg.act)
    y = jnp.einsum("becf,efd->becd", h, p["w2"].astype(dtype))
    y = shard(y, rules, "batch", "experts", "capacity", "d_model")

    # --- combine: gather each token's k expert outputs, weighted sum ---
    y_flat = y.reshape(b, e * cap, d)
    y_flat = jnp.concatenate([y_flat, jnp.zeros((b, 1, d), dtype)], axis=1)
    gath = jnp.take_along_axis(y_flat, slot[:, :, None], axis=1)    # (B,T,D)
    gath = gath.reshape(b, s, k, d)
    out = jnp.sum(gath * gate[..., None].astype(dtype), axis=2)
    return shard(out, rules, "batch", "seq", "d_model"), aux
