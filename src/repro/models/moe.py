"""Mixture-of-Experts with expert parallelism over the ``model`` mesh axis.

Dispatch is the FlooNoC "multi-stream DMA" analogue: tokens are sorted by
destination expert and moved in bulk (one wide grouped-GEMM per shard via
``jax.lax.ragged_dot``), instead of the [T, E, C] one-hot dispatch tensor.
Each expert shard processes its streams independently; results are combined
at the endpoint with a single psum (endpoint ordering, not in-network).

Implemented under ``jax.shard_map`` over the full mesh:
  * tokens: batch-sharded over the data axes, replicated over ``model``
  * routed experts: sharded over ``model`` (EP); shared experts: TP over ``model``
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.spec import PSpec
from repro.runtime import Runtime


def moe_schema(cfg: ModelConfig) -> dict:
    d, ff = cfg.d_model, cfg.moe_d_ff or cfg.d_ff
    sch = {
        "router": PSpec((d, cfg.n_experts), ("embed", None), "float32", "scaled:0"),
        "w1": PSpec((cfg.n_experts, d, ff), ("experts", "embed", "expert_mlp"), init="scaled:1"),
        "w3": PSpec((cfg.n_experts, d, ff), ("experts", "embed", "expert_mlp"), init="scaled:1"),
        "w2": PSpec((cfg.n_experts, ff, d), ("experts", "expert_mlp", "embed"), init="scaled:1"),
    }
    if cfg.n_shared_experts:
        ffs = ff * cfg.n_shared_experts
        sch["shared"] = {
            "w1": PSpec((d, ffs), ("embed", "mlp"), init="scaled:0"),
            "w3": PSpec((d, ffs), ("embed", "mlp"), init="scaled:0"),
            "w2": PSpec((ffs, d), ("mlp", "embed"), init="scaled:0"),
        }
    return sch


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _moe_local(p, x, *, cfg: ModelConfig, capacity_factor: float, n_shards: int,
               axis: str | None, batch_axes: tuple[str, ...] = ()):
    """Per-shard MoE body. x: [b_loc, S, d] (replicated over `axis`)."""
    b, S, d = x.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    E_loc = p["w1"].shape[0]  # experts on this shard
    T = b * S
    xf = x.reshape(T, d)

    # --- routing (f32) ---
    logits = xf.astype(jnp.float32) @ p["router"]  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)  # [T, k]
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # aux: load-balance loss (Switch-style) + router z-loss
    me = jnp.mean(probs, axis=0)  # [E]
    ce = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(1.0) / (T * k)
    lb_loss = E * jnp.sum(me * ce)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

    # --- dispatch: sort assignments by (mine, local expert id) ---
    my = 0 if axis is None else jax.lax.axis_index(axis)
    eid = top_e.reshape(-1)  # [T*k]
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    wgt = top_w.reshape(-1)
    local_e = eid - my * E_loc
    mine = (local_e >= 0) & (local_e < E_loc)
    sort_key = jnp.where(mine, local_e, E_loc)  # foreign -> bucket E_loc (last)
    order = jnp.argsort(sort_key)  # stable

    M = _round_up(max(int(capacity_factor * T * k * E_loc / E), 8), 8)
    M = min(M, T * k)
    ids = order[:M]
    sel_e = sort_key[ids]  # [M]; == E_loc for foreign/overflow rows
    sel_tok = tok[ids]
    sel_w = jnp.where(sel_e < E_loc, wgt[ids], 0.0)

    # group sizes within capacity; overflow+foreign rows folded into last group
    counts = jnp.bincount(sort_key, length=E_loc + 1)[:E_loc]
    cum = jnp.cumsum(counts)
    cum_cap = jnp.minimum(cum, M)
    gs = jnp.diff(jnp.concatenate([jnp.zeros((1,), cum.dtype), cum_cap]))
    gs = gs.at[E_loc - 1].add(M - cum_cap[-1])  # pad tail into last group
    gs = gs.astype(jnp.int32)
    dropped = jnp.sum(counts) - cum_cap[-1]  # assignments beyond capacity

    xg = xf[sel_tok].astype(p["w1"].dtype)  # [M, d]
    h = jax.nn.silu(jax.lax.ragged_dot(xg, p["w1"], gs)) * jax.lax.ragged_dot(xg, p["w3"], gs)
    y = jax.lax.ragged_dot(h, p["w2"], gs)  # [M, d]

    out = jnp.zeros((T, d), jnp.float32)
    out = out.at[sel_tok].add(y.astype(jnp.float32) * sel_w[:, None])

    # shared experts: TP over the same axis (ff dim sharded) -> partial sums
    if "shared" in p:
        sh = p["shared"]
        hs = jax.nn.silu(xf @ sh["w1"]) * (xf @ sh["w3"])
        out = out + (hs @ sh["w2"]).astype(jnp.float32)

    dropped_frac = dropped.astype(jnp.float32) / (T * k)
    if axis is not None:
        out = jax.lax.psum(out, axis)  # EP combine at the endpoint
        dropped_frac = jax.lax.psum(dropped_frac, axis)  # varies over model (capacity per shard)
    if batch_axes:
        # routing stats are invarying over `model` (tokens are replicated there);
        # averaging over the batch axes makes them fully replicated for out_specs P()
        lb_loss = jax.lax.pmean(lb_loss, batch_axes)
        z_loss = jax.lax.pmean(z_loss, batch_axes)
        dropped_frac = jax.lax.pmean(dropped_frac, batch_axes)

    aux = {
        "lb_loss": lb_loss,
        "router_z": z_loss,
        "dropped_frac": dropped_frac,
    }
    return out.reshape(b, S, d).astype(x.dtype), aux


def _moe_local_a2a(p, x, *, cfg: ModelConfig, capacity_factor: float,
                   axis: str, batch_axes: tuple[str, ...]):
    """All-to-all expert dispatch (perf variant, EXPERIMENTS.md §Perf).

    Tokens are batch-sharded over `axis` too (no replication): each shard
    routes its tokens, sorts them by destination expert shard, exchanges
    fixed-capacity slabs via all_to_all (the FlooNoC multi-stream DMA over
    the wide links), computes its local experts with one grouped GEMM, and
    returns results by the reverse all-to-all — ordering restored at the
    endpoint via the inverse permutation (RoB-less: static routes).
    """
    b, S, d = x.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    E_loc = p["w1"].shape[0]
    n_shards = E // E_loc
    my = jax.lax.axis_index(axis)
    T = b * S
    xf = x.reshape(T, d)

    logits = xf.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(1.0) / (T * k)
    lb_loss = E * jnp.sum(me * ce)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

    eid = top_e.reshape(-1)  # [T*k]
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    wgt = top_w.reshape(-1)
    dst_shard = eid // E_loc

    # pack into [n_shards, cap] send slabs (sorted by destination shard)
    cap = _round_up(max(int(capacity_factor * T * k / n_shards), 8), 8)
    order = jnp.argsort(dst_shard)
    pos_in_shard = jnp.arange(T * k) - jnp.searchsorted(
        dst_shard[order], dst_shard[order], side="left"
    )  # rank within its shard group (order-domain)
    slot = jnp.where(pos_in_shard < cap, dst_shard[order] * cap + pos_in_shard, -1)
    dropped = jnp.sum(slot < 0)

    def scatter(vals, fill):
        buf = jnp.full((n_shards * cap,) + vals.shape[1:], fill, vals.dtype)
        safe = jnp.where(slot >= 0, slot, n_shards * cap)  # OOB -> dropped
        return buf.at[safe].set(vals[order], mode="drop")

    x_send = scatter(xf[tok].astype(p["w1"].dtype), 0)
    e_send = scatter(eid, -1)
    t_send = scatter(tok, -1)

    # exchange slabs: [n_shards, cap, ...] -> received [n_shards, cap, ...]
    def a2a(v):
        v = v.reshape((n_shards, cap) + v.shape[1:])
        return jax.lax.all_to_all(v, axis, split_axis=0, concat_axis=0, tiled=False
                                  ).reshape((n_shards * cap,) + v.shape[2:])

    x_rcv, e_rcv, t_rcv = a2a(x_send), a2a(e_send), a2a(t_send)

    # group received rows by local expert
    local_e = jnp.where(e_rcv >= 0, e_rcv - my * E_loc, E_loc)
    order2 = jnp.argsort(local_e)
    M = n_shards * cap
    xg = x_rcv[order2]
    counts = jnp.bincount(local_e, length=E_loc + 1)[:E_loc]
    cum = jnp.minimum(jnp.cumsum(counts), M)
    gs = jnp.diff(jnp.concatenate([jnp.zeros((1,), cum.dtype), cum]))
    gs = gs.at[E_loc - 1].add(M - cum[-1])
    gs = gs.astype(jnp.int32)

    h = jax.nn.silu(jax.lax.ragged_dot(xg, p["w1"], gs)) * jax.lax.ragged_dot(xg, p["w3"], gs)
    y = jax.lax.ragged_dot(h, p["w2"], gs)
    y = jnp.zeros_like(y).at[order2].set(y)  # back to received-slab order

    # return trip + endpoint combine
    y_back = a2a(y)  # source-shard slab order restored by the reverse exchange
    w_slab = scatter(wgt, 0.0)
    t_slab = scatter(tok, 0)
    valid = scatter(jnp.ones_like(eid), 0) > 0
    out = jnp.zeros((T, d), jnp.float32)
    out = out.at[t_slab].add(
        jnp.where(valid[:, None], y_back.astype(jnp.float32) * w_slab[:, None], 0.0)
    )

    if "shared" in p:
        sh = p["shared"]
        hs = jax.nn.silu(xf @ sh["w1"]) * (xf @ sh["w3"])
        out = out + (hs @ sh["w2"]).astype(jnp.float32)

    dropped_frac = dropped.astype(jnp.float32) / (T * k)
    if batch_axes:
        lb_loss = jax.lax.pmean(lb_loss, batch_axes)
        z_loss = jax.lax.pmean(z_loss, batch_axes)
        dropped_frac = jax.lax.pmean(dropped_frac, batch_axes)
    aux = {"lb_loss": lb_loss, "router_z": z_loss, "dropped_frac": dropped_frac}
    return out.reshape(b, S, d).astype(x.dtype), aux


def _moe_block_a2a(p, x, *, cfg: ModelConfig, rt: Runtime):
    body = partial(
        _moe_local_a2a, cfg=cfg,
        capacity_factor=rt.moe_capacity_factor or cfg.moe_capacity_factor,
        axis=rt.axis_model, batch_axes=rt.batch_axes,
    )
    if rt.manual:
        return body(p, x)
    mesh = rt.mesh
    bspec = P(rt.batch_axes)
    pspecs = jax.tree.map(lambda _: P("model"), p)
    if "shared" in p:
        pspecs["shared"] = {"w1": P(None, None), "w3": P(None, None), "w2": P(None, None)}
    pspecs["router"] = P(None, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspecs, P(*bspec, None, None)),
        out_specs=(P(*bspec, None, None), P()),
        check_vma=False,  # replication over `model` holds numerically (the
        # return a2a restores source order) but is not statically inferable
    )(p, x)


def moe_block(p, x, *, cfg: ModelConfig, rt: Runtime):
    """x: [B, S, d] -> (out [B, S, d], aux dict of scalars)."""
    if rt.moe_impl == "a2a":
        return _moe_block_a2a(p, x, cfg=cfg, rt=rt)
    if rt.manual:
        # already inside an explicit shard_map over the whole mesh
        return _moe_local(
            p, x, cfg=cfg,
            capacity_factor=rt.moe_capacity_factor or cfg.moe_capacity_factor,
            n_shards=rt.n_model, axis=rt.axis_model, batch_axes=rt.batch_axes,
        )
    mesh = rt.mesh
    bspec = P(rt.batch_axes)
    body = partial(
        _moe_local,
        cfg=cfg,
        capacity_factor=rt.moe_capacity_factor or cfg.moe_capacity_factor,
        n_shards=rt.n_model,
        axis=rt.axis_model,
        batch_axes=rt.batch_axes,
    )
    pspecs = jax.tree.map(lambda _: P("model"), p)  # experts dim over model
    if "shared" in p:
        pspecs["shared"] = {
            "w1": P(None, "model"),
            "w3": P(None, "model"),
            "w2": P("model", None),
        }
    pspecs["router"] = P(None, None)
    out, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(pspecs, P(*bspec, None, None)),
        out_specs=(P(*bspec, None, None), P()),
    )(p, x)
    return out, aux
