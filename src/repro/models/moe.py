"""Mixture-of-Experts with paper-mapped dispatch strategies.

The paper's contribution is *moving computation to where the data lives* instead
of downloading data to the computation (location-aware Barnes-Hut), and this is
precisely the expert-parallel design choice:

  * ``move_data``    — the "old" algorithm: all-gather the expert weights onto
                       every token's shard (RMA-download analogue).
  * ``move_compute`` — the "new" algorithm: all_to_all the *tokens* (the 42-byte
                       request analogue) to the shard owning the expert, compute
                       there, all_to_all the results back (9-byte response).
  * ``local``        — experts replicated (single device / smoke tests).
  * ``auto``         — napkin-math chooser: pick whichever strategy moves fewer
                       bytes for this (arch, shape, mesh) — the paper's principle
                       generalized into a cost model (see DESIGN.md §3).

All strategies share one sort-based local dispatch engine and produce identical
outputs when capacity is not exceeded (tested in tests/test_moe.py).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dtype_of, init_mlp, apply_mlp


# ------------------------------------------------------------ params
def init_moe(key, cfg: ModelConfig, d: int):
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    dt = dtype_of(cfg)
    e, ff = cfg.num_experts, cfg.d_ff
    s_in, s_out = d ** -0.5, ff ** -0.5
    p = {
        "router": (jax.random.normal(k1, (d, e)) * s_in).astype(jnp.float32),
        "w_up": (jax.random.normal(k2, (e, d, ff)) * s_in).astype(dt),
        "w_down": (jax.random.normal(k3, (e, ff, d)) * s_out).astype(dt),
    }
    if cfg.mlp_gated:
        p["w_gate"] = (jax.random.normal(k4, (e, d, ff)) * s_in).astype(dt)
    if cfg.moe_dense_residual:
        p["dense"] = init_mlp(k5, cfg, d, cfg.d_ff)
    return p


# ------------------------------------------------------------ routing
def topk_routing(router_w, x2d, k: int):
    """x2d: (T, d) -> gates (T, k) f32 (renormalized), expert ids (T, k) i32,
    plus the load-balancing aux loss (Switch-style)."""
    logits = x2d.astype(jnp.float32) @ router_w          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    e = router_w.shape[1]
    # aux: mean prob per expert x fraction of tokens routed to expert
    frac_prob = jnp.mean(probs, axis=0)
    onehot_top1 = jax.nn.one_hot(experts[:, 0], e, dtype=jnp.float32)
    frac_tok = jnp.mean(onehot_top1, axis=0)
    aux = e * jnp.sum(frac_prob * frac_tok)
    return gates, experts, aux


def positions_within(ids, num_buckets: int):
    """Rank of each element within its bucket (stable, sort-based).
    ids: (N,) int32 in [0, num_buckets). Returns (N,) int32."""
    n = ids.shape[0]
    order = jnp.argsort(ids, stable=True)
    sorted_ids = ids[order]
    first = jnp.searchsorted(sorted_ids, jnp.arange(num_buckets), side="left")
    ranks = jnp.arange(n, dtype=jnp.int32) - first[sorted_ids].astype(jnp.int32)
    return jnp.zeros((n,), jnp.int32).at[order].set(ranks)


def _capacity(n_tokens: int, k: int, buckets: int, factor: float, minimum=4):
    c = int(math.ceil(n_tokens * k / buckets * factor))
    return max(minimum, -(-c // 8) * 8)  # round up to 8 lanes


# ------------------------------------------------------------ local engine
def _expert_ffn(w_gate, w_up, w_down, cfg: ModelConfig, buf):
    """buf: (E, C, d) -> (E, C, d)."""
    up = jnp.einsum("ecd,edf->ecf", buf, w_up)
    if cfg.mlp_gated:
        up = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * up
    else:
        up = jax.nn.gelu(up)
    return jnp.einsum("ecf,efd->ecd", up, w_down)


def moe_local(p_router, w_gate, w_up, w_down, cfg: ModelConfig, x2d,
              capacity_factor=None):
    """All experts resident locally. x2d: (T, d) -> (T, d), aux."""
    t, d = x2d.shape
    e, k = cfg.num_experts, cfg.top_k
    cf = capacity_factor or cfg.capacity_factor
    gates, experts, aux = topk_routing(p_router, x2d, k)
    cap = _capacity(t, k, e, cf)

    flat_e = experts.reshape(-1)                          # (T*k,)
    pos = positions_within(flat_e, e)
    keep = pos < cap
    pos_c = jnp.where(keep, pos, cap)                     # OOB scatter -> dropped
    tok_idx = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    buf = jnp.zeros((e, cap, d), x2d.dtype)
    buf = buf.at[flat_e, pos_c].set(x2d[tok_idx], mode="drop")
    out_buf = _expert_ffn(w_gate, w_up, w_down, cfg, buf)
    y_tok = out_buf.at[flat_e, pos_c].get(mode="fill", fill_value=0.0)
    y_tok = y_tok * keep[:, None]
    y = jnp.sum((y_tok.reshape(t, k, d).astype(jnp.float32)
                 * gates[..., None]), axis=1)
    return y.astype(x2d.dtype), aux


# ------------------------------------------------------------ sharded engines
def _gather_over(axis_name, w, axis):
    """FSDP all-gather of a weight slice along ``axis`` over mesh axis."""
    if w is None:
        return None
    return jax.lax.all_gather(w, axis_name, axis=axis, tiled=True)


def moe_move_data(p, cfg: ModelConfig, x2d, *, model_axis="model",
                  data_axes=("data",)):
    """Paper's OLD pattern inside shard_map: all-gather expert weights to every
    shard (download the data), then compute locally."""
    # weights arrive sharded (E/model, d/data, ff); gather both axes fully
    def g(w, shard_axis):
        if w is None:
            return None
        w = jax.lax.all_gather(w, model_axis, axis=0, tiled=True)
        for ax in data_axes:
            w = jax.lax.all_gather(w, ax, axis=shard_axis, tiled=True)
        return w
    w_up = g(p["w_up"], 1)
    w_down = g(p["w_down"], 1)
    w_gate = g(p.get("w_gate"), 1)
    return moe_local(p["router"], w_gate, w_up, w_down, cfg, x2d)


def moe_move_compute(p, cfg: ModelConfig, x2d, *, model_axis="model",
                     data_axes=("data",)):
    """Paper's NEW pattern: ship tokens (requests) to the expert's owner shard,
    compute there, ship results (responses) back. Two all_to_alls, no weight
    movement across the model axis."""
    t, d = x2d.shape
    e, k = cfg.num_experts, cfg.top_k
    p_sz = jax.lax.axis_size(model_axis)
    e_loc = e // p_sz
    assert e % p_sz == 0, (e, p_sz)

    # local experts: undo fsdp sharding over data axes only (E_loc slice stays)
    def g(w):
        if w is None:
            return None
        for ax in data_axes:
            w = jax.lax.all_gather(w, ax, axis=1, tiled=True)
        return w
    w_up, w_down, w_gate = g(p["w_up"]), g(p["w_down"]), g(p.get("w_gate"))

    gates, experts, aux = topk_routing(p["router"], x2d, k)

    # ---- build per-peer request buffers (the 42-byte request analogue) ----
    flat_e = experts.reshape(-1).astype(jnp.int32)        # (N=T*k,)
    peer = flat_e // e_loc                                # owning shard
    cap_p = _capacity(t, k, p_sz, cfg.capacity_factor)
    pos_p = positions_within(peer, p_sz)
    keep = pos_p < cap_p
    pos_pc = jnp.where(keep, pos_p, cap_p)
    tok_idx = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)

    send_tok = jnp.zeros((p_sz, cap_p, d), x2d.dtype)
    send_tok = send_tok.at[peer, pos_pc].set(x2d[tok_idx], mode="drop")
    send_e = jnp.full((p_sz, cap_p), -1, jnp.int32)
    send_e = send_e.at[peer, pos_pc].set(flat_e % e_loc, mode="drop")

    recv_tok = jax.lax.all_to_all(send_tok, model_axis, 0, 0, tiled=True)
    recv_e = jax.lax.all_to_all(send_e, model_axis, 0, 0, tiled=True)

    # ---- owner-side computation (the "calculation request" handler) ----
    r_tok = recv_tok.reshape(p_sz * cap_p, d)
    r_e = recv_e.reshape(p_sz * cap_p)
    valid = r_e >= 0
    r_e_c = jnp.where(valid, r_e, 0)
    cap_e = _capacity(p_sz * cap_p, 1, e_loc, cfg.capacity_factor)
    pos_e = positions_within(jnp.where(valid, r_e_c, e_loc), e_loc + 1)
    keep_e = valid & (pos_e < cap_e)
    pos_ec = jnp.where(keep_e, pos_e, cap_e)
    buf = jnp.zeros((e_loc, cap_e, d), x2d.dtype)
    buf = buf.at[r_e_c, pos_ec].set(r_tok, mode="drop")
    out_buf = _expert_ffn(w_gate, w_up, w_down, cfg, buf)
    r_out = out_buf.at[r_e_c, pos_ec].get(mode="fill", fill_value=0.0)
    r_out = r_out * keep_e[:, None]

    # ---- responses travel back (the 9-byte response analogue) ----
    send_back = r_out.reshape(p_sz, cap_p, d)
    recv_back = jax.lax.all_to_all(send_back, model_axis, 0, 0, tiled=True)
    y_tok = recv_back.at[peer, pos_pc].get(mode="fill", fill_value=0.0)
    y_tok = y_tok * keep[:, None]
    y = jnp.sum((y_tok.reshape(t, k, d).astype(jnp.float32)
                 * gates[..., None]), axis=1)
    return y.astype(x2d.dtype), aux


# ------------------------------------------------------------ cost model
def moe_strategy_cost(cfg: ModelConfig, t_local: int, model_size: int,
                      bytes_per_el=2):
    """Bytes crossing the model axis per device per layer, fwd only.
    The 'auto' chooser (paper principle as a cost model) picks the min."""
    e = cfg.num_experts
    e_loc = max(1, e // max(model_size, 1))
    n_mats = 3 if cfg.mlp_gated else 2
    w_bytes = (e - e_loc) * n_mats * cfg.d_model * cfg.d_ff * bytes_per_el
    frac_remote = (model_size - 1) / max(model_size, 1)
    tok_bytes = 2 * t_local * cfg.top_k * cfg.d_model * bytes_per_el * frac_remote
    return {"move_data": w_bytes, "move_compute": tok_bytes}


def choose_strategy(cfg: ModelConfig, t_local: int, model_size: int) -> str:
    c = moe_strategy_cost(cfg, t_local, model_size)
    return "move_data" if c["move_data"] < c["move_compute"] else "move_compute"


# ------------------------------------------------------------ entry point
def apply_moe(p, cfg: ModelConfig, x, *, mesh=None, strategy=None):
    """x: (B, S, d) -> (y, aux). Dispatches per cfg.parallel.moe_strategy."""
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    strategy = strategy or cfg.parallel.moe_strategy
    model_size = 1
    axis_names = ()
    if mesh is not None:
        model_size = mesh.shape.get("model", 1)
        axis_names = tuple(mesh.axis_names)
    ndev = math.prod(mesh.shape.values()) if mesh is not None else 1
    if strategy == "auto":
        t_local = (b * s) // max(1, ndev)
        strategy = choose_strategy(cfg, t_local, model_size) \
            if model_size > 1 else "local"
    if mesh is None or model_size <= 1 or strategy == "local":
        y, aux = moe_local(p["router"], p.get("w_gate"), p["w_up"], p["w_down"],
                           cfg, x2d)
    else:
        data_axes = tuple(a for a in axis_names if a != "model")
        wspec2 = jax.sharding.PartitionSpec(
            "model", data_axes if data_axes else None, None)
        p_moe = {k: v for k, v in p.items() if k != "dense"}
        in_specs = {k: (jax.sharding.PartitionSpec() if k == "router" else wspec2)
                    for k in p_moe}
        fn = moe_move_data if strategy == "move_data" else moe_move_compute
        from repro.parallel import sharding as shd
        tok_axes = shd.batch_axes(mesh, cfg.parallel.layout)
        x_spec = jax.sharding.PartitionSpec(
            tok_axes if tok_axes else None, None)
        # tokens additionally split over the model axis INSIDE the body —
        # otherwise all model shards redundantly compute identical expert FFNs
        # (16x waste at 16-way TP). Done with slice + all_gather rather than a
        # jit-boundary reshard, which GSPMD handles pathologically (full
        # remat). In 'fsdp' layout tokens already arrive model-split.
        split_model = ("model" not in tok_axes
                       and (b * s) % ndev == 0 and model_size > 1)

        def body(p_, x2d_):
            x_in = x2d_
            if split_model:
                t_m = x2d_.shape[0] // model_size
                idx = jax.lax.axis_index("model")
                x_in = jax.lax.dynamic_slice_in_dim(x2d_, idx * t_m, t_m, 0)
            y_, aux_ = fn(p_, cfg, x_in, model_axis="model",
                          data_axes=data_axes)
            if split_model:
                y_ = jax.lax.all_gather(y_, "model", axis=0, tiled=True)
            for ax in mesh.axis_names:       # replicate aux across the mesh
                aux_ = jax.lax.pmean(aux_, ax)
            return y_, aux_

        y, aux = jax.shard_map(
            body, mesh=mesh, in_specs=(in_specs, x_spec),
            out_specs=(x_spec, jax.sharding.PartitionSpec()),
            check_vma=False)(p_moe, x2d)
    if cfg.moe_dense_residual:
        y = y + apply_mlp(p["dense"], cfg, x2d)
    return y.reshape(b, s, d), aux
