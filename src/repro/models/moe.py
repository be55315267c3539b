"""Mixture-of-Experts FFN: sort-based capacity dispatch, expert-parallel.

Design (DESIGN.md §6): tokens are grouped per data shard; within a group the
routing sort/gather is *local* (activations are replicated across the 'model'
axis inside a data row), experts are sharded over 'model', and only the
combine reduces across 'model' — preserving the paper's four-syncs-per-layer
structure (§5.1) with MoE swapped in for the dense FFN.

Memory is O(G·E·C·d) for the dispatch buffers — never O(T·E·C); the one-hot
dispatch-einsum formulation of T5X-style MoE would be ~1e13 elements for the
kimi-k2 prefill cell and is deliberately avoided.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import ParamDef
from repro.models.layers import activation
from repro.sharding.axes import constrain, _current_mesh, MeshInfo, logical_spec


def moe_defs(cfg: ModelConfig, stacked: Optional[int] = None) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    lead = () if stacked is None else (stacked,)
    la = () if stacked is None else ("layers",)
    # fsdp_params: the expert d_ff dim additionally shards over 'data'
    # (ZeRO-3-style 2D residence). The GSPMD path all-gathers one layer's
    # experts inside the scan; the EP path (apply_moe_ep) computes on the
    # resident slices directly — SAME unified layout serves both.
    ff = "fsdp" if cfg.fsdp_params else "d_ff"
    return {
        "router": ParamDef(lead + (d, e), la + ("d_model", None), "small_normal"),
        "wi": ParamDef(lead + (e, d, f), la + ("experts", "d_model", ff)),
        "wg": ParamDef(lead + (e, d, f), la + ("experts", "d_model", ff)),
        "wo": ParamDef(lead + (e, f, d), la + ("experts", ff, "d_model")),
    }


def _num_groups(batch: int, mesh) -> int:
    """One routing group per data shard (sort/gather stay local)."""
    if mesh is None:
        return 1
    info = MeshInfo(mesh)
    g = 1
    for ax in ("pod", "data"):
        e = info.axis_sizes.get(ax, 1)
        if batch % (g * e) == 0:
            g *= e
    return g


def capacity(tokens_per_group: int, k: int, num_experts: int, cf: float) -> int:
    c = int(-(-(tokens_per_group * k * cf) // num_experts))  # ceil
    return max(1, min(c, tokens_per_group * k))


def route(router_logits: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """top-k routing. router_logits: (..., E) -> (weights (...,k), idx (...,k))."""
    weights, idx = jax.lax.top_k(router_logits, k)
    weights = jax.nn.softmax(weights.astype(jnp.float32), axis=-1)
    return weights, idx


def load_balance_loss(router_probs: jax.Array, expert_idx: jax.Array,
                      num_experts: int) -> jax.Array:
    """Switch-style auxiliary loss: E * <fraction routed> . <mean prob>."""
    probs_mean = jnp.mean(router_probs, axis=tuple(range(router_probs.ndim - 1)))
    one_hot = jax.nn.one_hot(expert_idx[..., 0], num_experts, dtype=jnp.float32)
    frac = jnp.mean(one_hot, axis=tuple(range(one_hot.ndim - 1)))
    return num_experts * jnp.sum(frac * probs_mean)


def _dispatch_tables(expert_idx: jax.Array, k: int, E: int, C: int):
    """Build (E, C) gather tables from per-token top-k expert assignments.

    expert_idx: (T, k) int32. Returns:
      token_for_slot (E, C): flat token index feeding each expert slot
                             (sentinel T for empty slots),
      slot_weight_sel (E, C): index into the flattened (T*k,) weights,
      valid (E, C) bool.
    """
    T = expert_idx.shape[0]
    flat_e = expert_idx.reshape(-1)                        # (T*k,)
    order = jnp.argsort(flat_e, stable=True)               # tokens grouped by expert
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=E)                # (E,)
    starts = jnp.cumsum(counts) - counts                   # exclusive
    pos_in_e = jnp.arange(T * k) - starts[sorted_e]        # slot within expert
    valid_src = pos_in_e < C
    # scatter sorted entries into the (E, C) table; invalid -> dropped
    slot = jnp.where(valid_src, pos_in_e, C)
    table = jnp.full((E, C + 1), T * k, jnp.int32)
    table = table.at[sorted_e, slot].set(order.astype(jnp.int32), mode="drop")
    table = table[:, :C]                                   # (E, C)
    valid = table < T * k
    token_for_slot = jnp.where(valid, table // k, T)
    return token_for_slot, table, valid


def apply_moe_ep(cfg: ModelConfig, p: dict, x: jax.Array,
                 mesh) -> Tuple[jax.Array, jax.Array]:
    """Resident expert-parallel MoE (shard_map) — the kimi decode hillclimb
    (EXPERIMENTS.md §Perf iteration A).

    Weights stay 2D-sharded (experts over 'model', d_ff over 'data' via the
    'fsdp' axis) and are NEVER gathered; instead the (tiny) token set is
    all-gathered to every device, each device computes its expert-subset x
    d_ff-slice, partial outputs psum over 'data' (f slices), and the
    combined expert contributions psum over 'model'. Per-step collective
    payload drops from O(params) to O(tokens x d) — ~250x for kimi-1T
    decode (napkin math in the §Perf log)."""
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    info = MeshInfo(mesh)
    m_ext = info.axis_sizes.get("model", 1)
    d_ext = info.axis_sizes.get("data", 1)
    assert E % m_ext == 0 and cfg.d_ff % max(1, d_ext) == 0
    T = B * S
    C = capacity(T, k, E, cfg.capacity_factor)
    data_axes = tuple(a for a in ("pod", "data") if a in info.axis_sizes)

    x_spec = logical_spec(x.shape, ("batch", "seq", "d_model"), mesh)
    w_in_spec = logical_spec(p["wi"].shape,
                             ("experts", "d_model", "fsdp"), mesh)
    w_out_spec = logical_spec(p["wo"].shape,
                              ("experts", "fsdp", "d_model"), mesh)
    r_spec = P(None, None)

    def body(x_l, wr, wi, wg, wo):
        # gather ALL tokens everywhere (decode-scale T: a few MB)
        x_all = x_l
        for ax in data_axes:
            x_all = jax.lax.all_gather(x_all, ax, axis=0, tiled=True)
        xf = x_all.reshape(-1, d)                        # (T, d)
        logits = (xf @ wr).astype(jnp.float32)           # router replicated
        weights, idx = route(logits, k)
        probs = jax.nn.softmax(logits, axis=-1)
        aux = load_balance_loss(probs, idx, E)
        # local experts for this 'model' shard
        e_loc = wi.shape[0]
        shard = jax.lax.axis_index("model") if m_ext > 1 else 0
        local_idx = idx - shard * e_loc                  # in [0, e_loc) if ours
        ours = (local_idx >= 0) & (local_idx < e_loc)
        masked = jnp.where(ours, local_idx, e_loc)       # sentinel
        token_for_slot, weight_sel, valid = _dispatch_tables(
            jnp.where(ours, local_idx, e_loc + 1).astype(jnp.int32),
            k, e_loc, min(C, T * k))
        x_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], 0)
        inp = x_pad[token_for_slot]                      # (e_loc, C, d)
        w_flat = jnp.concatenate(
            [weights.reshape(-1), jnp.zeros((1,), weights.dtype)], 0)
        w_slot = w_flat[jnp.where(valid, weight_sel, T * k)]
        # d_ff slice local: contraction over full d, f-partial
        h = jnp.einsum("ecd,edf->ecf", inp, wi)
        g = jnp.einsum("ecd,edf->ecf", inp, wg)
        h = activation(cfg, g) * h                       # (e_loc, C, f/dp)
        z = jnp.einsum("ecf,efd->ecd", h, wo)            # partial over f
        for ax in reversed(data_axes):                   # sum f slices
            z = jax.lax.psum(z, ax)
        z = z * w_slot[..., None].astype(z.dtype)
        y = jnp.zeros((T + 1, d), z.dtype)
        y = y.at[token_for_slot.reshape(-1)].add(z.reshape(-1, d),
                                                 mode="drop")[:T]
        if m_ext > 1:
            y = jax.lax.psum(y, "model")                 # combine experts
        # return this shard's token slice (undo the all-gather)
        t_loc = x_l.shape[0] * x_l.shape[1]
        start = 0
        mult = 1
        for ax in reversed(data_axes):
            start = start + jax.lax.axis_index(ax) * mult
            mult = mult * info.axis_sizes[ax]
        y_loc = jax.lax.dynamic_slice_in_dim(y, start * t_loc, t_loc, 0)
        return y_loc.reshape(x_l.shape), aux

    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, r_spec, w_in_spec, w_in_spec, w_out_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, p["router"], p["wi"], p["wg"], p["wo"])
    return y, aux


def apply_moe(cfg: ModelConfig, p: dict, x: jax.Array,
              mesh=None) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (y (B, S, d), aux_loss scalar)."""
    mesh = mesh or _current_mesh()
    if cfg.moe_impl == "ep" and mesh is not None and not mesh.empty:
        return apply_moe_ep(cfg, p, x, mesh)
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    G = _num_groups(B, mesh)
    Tg = (B // G) * S
    C = capacity(Tg, k, E, cfg.capacity_factor)

    xg = x.reshape(G, Tg, d)
    xg = constrain(xg, ("batch", None, "d_model"))

    logits = jnp.einsum("gtd,de->gte", xg, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = route(logits, k)                        # (G,Tg,k) each
    aux = load_balance_loss(probs, idx, E)

    def per_group(xg_1, idx_1, w_1):
        # xg_1: (Tg, d); idx_1: (Tg, k); w_1: (Tg, k)
        token_for_slot, weight_sel, valid = _dispatch_tables(idx_1, k, E, C)
        x_pad = jnp.concatenate([xg_1, jnp.zeros((1, d), xg_1.dtype)], 0)
        inp = x_pad[token_for_slot]                        # (E, C, d) gather
        w_flat = jnp.concatenate(
            [w_1.reshape(-1), jnp.zeros((1,), w_1.dtype)], 0)
        w_slot = w_flat[jnp.where(valid, weight_sel, Tg * k)]   # (E, C)
        return inp, token_for_slot, w_slot

    inp, token_for_slot, w_slot = jax.vmap(per_group)(xg, idx, weights)
    # (G, E, C, d) — experts sharded over 'model', group over data axes
    inp = constrain(inp, ("batch", "experts", None, "d_model"))

    # fsdp_params: resident weights are ZeRO-3 sharded over 'data'; gather
    # them HERE (inside the layer-scan body) so the all-gather is per-layer
    # and transient, not hoisted over the whole stacked tensor.
    wi, wg, wo = p["wi"], p["wg"], p["wo"]
    if cfg.fsdp_params:
        wi = constrain(wi, ("experts", "d_model", "d_ff"))
        wg = constrain(wg, ("experts", "d_model", "d_ff"))
        wo = constrain(wo, ("experts", "d_ff", "d_model"))

    h = jnp.einsum("gecd,edf->gecf", inp, wi)
    g = jnp.einsum("gecd,edf->gecf", inp, wg)
    h = activation(cfg, g) * h
    h = constrain(h, ("batch", "experts", None, "d_ff"))
    out = jnp.einsum("gecf,efd->gecd", h, wo)              # (G, E, C, d)
    out = out * w_slot[..., None].astype(out.dtype)

    def combine(out_1, token_for_slot_1):
        # scatter-add expert slots back to tokens; sentinel Tg rows dropped
        y = jnp.zeros((Tg + 1, d), out_1.dtype)
        y = y.at[token_for_slot_1.reshape(-1)].add(
            out_1.reshape(-1, d), mode="drop")
        return y[:Tg]

    y = jax.vmap(combine)(out, token_for_slot)             # (G, Tg, d)
    y = constrain(y, ("batch", None, "d_model"))           # all-reduce over model
    return y.reshape(B, S, d), aux
