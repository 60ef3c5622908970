"""Serving: prefill (full-sequence forward producing state) and single-token
decode steps for every block kind.

State layouts (static shapes):
  attn (full)    : k, v (B, Hkv, S_max, hd)          slot = position
  attn (window)  : k, v (B, Hkv, W, hd)  ring buffer  slot = position % W
  rglru          : h (B, W), conv_tail (B, K-1, W)
  mlstm / slstm  : recurrent dicts from repro.models.ssm

``decode_attention='split_kv'`` shards the full KV cache's sequence axis over
the model axis and combines per-shard partial softmax stats with a psum — the
paper's move-compute pattern (ship the tiny (o,m,l) response, not the cache).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (apply_mlp, apply_norm, apply_rope, dtype_of,
                                 embed_tokens, lm_logits, sinusoidal_positions)
from repro.models.transformer import _project_qkv, ffn_block, _rms_head
from repro.parallel import sharding as shd


# ================================================================ state init
def _attn_cache(cfg: ModelConfig, batch: int, max_seq: int):
    s = cfg.attn_window if cfg.attn_window else max_seq
    dt = dtype_of(cfg)
    shape = (batch, cfg.num_kv_heads, s, cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def init_layer_state(cfg: ModelConfig, kind: str, batch: int, max_seq: int):
    if kind == "attn":
        return _attn_cache(cfg, batch, max_seq)
    if kind == "rglru":
        return rglru_lib.rglru_init_state(cfg, batch, cfg.d_model)
    if kind == "mlstm":
        return ssm_lib.mlstm_init_state(cfg, batch)
    if kind == "slstm":
        return ssm_lib.slstm_init_state(cfg, batch, cfg.d_model)
    raise ValueError(kind)


def init_decode_state(cfg: ModelConfig, batch: int, max_seq: int):
    pattern = cfg.pattern()
    if cfg.scan_layers and len(set(pattern)) == 1 and pattern[0] == "attn":
        one = _attn_cache(cfg, batch, max_seq)
        layers = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.num_layers,) + x.shape), one)
    else:
        layers = [init_layer_state(cfg, k, batch, max_seq) for k in pattern]
    return {"pos": jnp.zeros((), jnp.int32), "layers": layers}


def state_shardings(cfg: ModelConfig, state_shapes, mesh, batch: int):
    """Sharding rules for the decode state (dry-run in_shardings)."""
    import math as _math
    baxes = shd.batch_axes(mesh)
    bsize = _math.prod(mesh.shape[a] for a in baxes) if baxes else 1
    stacked = not isinstance(state_shapes.get("layers"), list)
    split_kv = cfg.parallel.decode_attention == "split_kv" and \
        mesh.shape.get("model", 1) > 1 and not cfg.attn_window

    def one(path, leaf):
        name = shd._path_str(path)
        nd = len(leaf.shape)
        if name.endswith("pos"):
            return NamedSharding(mesh, P())
        off = 1 if (stacked and name.startswith("layers")) else 0
        spec = [None] * nd
        if nd > off and leaf.shape[off] % max(bsize, 1) == 0 and \
                leaf.shape[off] >= bsize:
            spec[off] = baxes
        if split_kv and (name.endswith("/k") or name.endswith("/v")) and \
                nd == off + 4 and leaf.shape[off + 2] % mesh.shape["model"] == 0:
            spec[off + 2] = "model"
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(one, state_shapes)


# ================================================================ attn decode
def _ring_positions(cfg: ModelConfig, pos, cache_slots: int):
    """Global position held by each cache slot after writing position ``pos``."""
    slots = jnp.arange(cache_slots)
    if cfg.attn_window:
        w = cache_slots
        return pos - ((pos - slots) % w)
    return slots


def attn_block_decode(p, cfg: ModelConfig, x_t, cache, pos, mesh):
    """x_t: (B, d); cache k/v (B,Hkv,S,hd); pos scalar. -> (y, new cache)."""
    b, d = x_t.shape
    h = apply_norm(cfg, p["ln1"], x_t[:, None, :])
    q, k, v = _project_qkv(p["attn"], cfg, h, pos[None])
    q = q[:, :, 0, :]                                    # (B,Hq,hd)
    s_cache = cache["k"].shape[2]
    slot = pos % s_cache if cfg.attn_window else pos
    new_k = jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, slot, 0))
    new_v = jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, slot, 0))
    kv_pos = _ring_positions(cfg, pos, s_cache)
    use_split = (cfg.parallel.decode_attention == "split_kv" and mesh is not None
                 and mesh.shape.get("model", 1) > 1 and not cfg.attn_window
                 and s_cache % mesh.shape["model"] == 0)
    if use_split:
        import math as _math
        baxes = shd.batch_axes(mesh)
        bsize = _math.prod(mesh.shape[a] for a in baxes) if baxes else 1
        bspec = baxes if (bsize > 0 and b % bsize == 0) else None

        def body(q_, k_, v_):
            s_loc = k_.shape[2]
            off = jax.lax.axis_index("model") * s_loc
            kvp = off + jnp.arange(s_loc)
            o, m, l = attn_lib.decode_attention(
                q_, k_, v_, kvp, pos + 1, window=cfg.attn_window,
                softcap=cfg.attn_logit_softcap)
            return attn_lib.combine_partial(o, m, l, "model")

        o = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(bspec, None, None), P(bspec, None, "model", None),
                      P(bspec, None, "model", None)),
            out_specs=P(bspec, None, None), check_vma=False)(q, new_k, new_v)
    else:
        o, m, l = attn_lib.decode_attention(
            q, new_k, new_v, kv_pos, pos + 1, window=cfg.attn_window,
            softcap=cfg.attn_logit_softcap)
        o = attn_lib.finalize_partial(o, m, l)
    y = (o.reshape(b, cfg.q_dim).astype(x_t.dtype) @ p["attn"]["wo"])
    return x_t + y, {"k": new_k, "v": new_v}


def apply_layer_decode(p, cfg: ModelConfig, kind, x_t, lstate, pos, mesh):
    if kind == "mlstm":
        return ssm_lib.mlstm_step(p["kind_mlstm"], cfg, x_t, lstate)
    if kind == "slstm":
        return ssm_lib.slstm_step(p["kind_slstm"], cfg, x_t, lstate)
    if kind == "attn":
        x_t, lstate = attn_block_decode(p, cfg, x_t, lstate, pos, mesh)
    elif kind == "rglru":
        x_t, lstate = rglru_lib.rglru_step(p["rec"], cfg, x_t, lstate)
    if cfg.d_ff:
        x3, _ = ffn_block(p, cfg, x_t[:, None, :], mesh)
        x_t = x3[:, 0, :]
    return x_t, lstate


def decode_step(params, cfg: ModelConfig, state, tokens, *, mesh=None):
    """One token for every sequence. tokens: (B,) int32 -> (logits (B,V), state)."""
    pos = state["pos"]
    x = embed_tokens(params["embed"], tokens)            # (B, d)
    if cfg.rotary_pct == 0:
        d = x.shape[-1]
        pe = sinusoidal_positions(1, d, 0)[0]            # static stub table
        x = (x.astype(jnp.float32) + pe).astype(x.dtype)
    x = shd.constrain(x, ("batch", None))

    if "layers_stacked" in params:
        def body(x_c, xs):
            layer_p, layer_s = xs
            x_n, s_n = apply_layer_decode(layer_p, cfg, "attn", x_c, layer_s,
                                          pos, mesh)
            return x_n, s_n
        x, new_layers = jax.lax.scan(body, x,
                                     (params["layers_stacked"],
                                      state["layers"]))
    else:
        pattern = cfg.pattern()
        new_layers = []
        for i, layer_p in enumerate(params["layers"]):
            x, s_n = apply_layer_decode(layer_p, cfg, pattern[i], x,
                                        state["layers"][i], pos, mesh)
            new_layers.append(s_n)
    x = apply_norm(cfg, params["final_norm"], x)
    logits = lm_logits(params["head"], params["embed"], cfg, x)
    logits = shd.constrain(logits, ("batch", "model"))
    return logits, {"pos": pos + 1, "layers": new_layers}


# ================================================================ prefill
def _attn_prefill(p, cfg: ModelConfig, x, positions):
    from repro.models.transformer import attn_block_full
    h = apply_norm(cfg, p["ln1"], x)
    q, k, v = _project_qkv(p["attn"], cfg, h, positions)
    o = attn_lib.chunked_attention(
        q, k, v, causal=True, window=cfg.attn_window,
        q_positions=positions, kv_positions=positions,
        softcap=cfg.attn_logit_softcap)
    b, hq, s, hd = o.shape
    o = o.transpose(0, 2, 1, 3).reshape(b, s, hq * hd)
    x = x + o @ p["attn"]["wo"]
    if cfg.attn_window:
        w = cfg.attn_window
        s_len = positions.shape[0]
        if s_len >= w:
            # last w positions; position p = s-w+i sits at slot p % w
            k, v = k[:, :, -w:, :], v[:, :, -w:, :]
            roll = s_len % w
            k = jnp.roll(k, roll, axis=2)
            v = jnp.roll(v, roll, axis=2)
        else:
            # prompt shorter than the window: slots == positions, pad the ring
            pad = ((0, 0), (0, 0), (0, w - s_len), (0, 0))
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    return x, {"k": k, "v": v}


def _rglru_prefill(p, cfg, x):
    y = rglru_lib.rglru_forward(p["rec"], cfg, x)
    # recompute final state cheaply: run last conv window through step form
    xn = apply_norm(cfg, p["rec"]["norm"], x)
    xb = (xn @ p["rec"]["w_x"]).astype(jnp.float32)
    xc = rglru_lib._conv1d_causal(xb, p["rec"]["conv"], p["rec"]["conv_bias"])
    log_a, i_g = rglru_lib._gates(p["rec"], xc)
    a = jnp.exp(log_a)
    bterm = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * (i_g * xc)

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, bl * ar + br
    _, h = jax.lax.associative_scan(combine, (a, bterm), axis=1)
    kw = cfg.rglru_conv_width - 1
    state = {"h": h[:, -1, :], "conv_tail": xb[:, -kw:, :]}
    return y, state


def prefill(params, cfg: ModelConfig, tokens, *, extra_embeds=None, mesh=None,
            pad_cache_to=0):
    """Full-sequence forward that also returns the decode state.
    Returns (last-position logits (B,V), state)."""
    x = embed_tokens(params["embed"], tokens)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    b, s, d = x.shape
    positions = jnp.arange(s)
    if cfg.rotary_pct == 0:
        x = (x.astype(jnp.float32) + sinusoidal_positions(s, d)).astype(x.dtype)
    x = shd.constrain(x, ("batch", None, None))
    pattern = cfg.pattern()

    def run_layer(layer_p, kind, xc):
        if kind == "attn":
            xc, st = _attn_prefill(layer_p, cfg, xc, positions)
        elif kind == "rglru":
            xc, st = _rglru_prefill(layer_p, cfg, xc)
        elif kind == "mlstm":
            # run full scan then recompute state from scratch (scan w/ carry out)
            xc2 = ssm_lib.mlstm_scan(layer_p["kind_mlstm"], cfg, xc)
            st = _mlstm_final_state(layer_p["kind_mlstm"], cfg, xc)
            xc = xc2
        elif kind == "slstm":
            xc2 = ssm_lib.slstm_scan(layer_p["kind_slstm"], cfg, xc)
            st = _slstm_final_state(layer_p["kind_slstm"], cfg, xc)
            xc = xc2
        else:
            raise ValueError(kind)
        if cfg.d_ff and kind in ("attn", "rglru"):
            xc, _ = ffn_block(layer_p, cfg, xc, mesh)
        return shd.constrain(xc, ("batch", None, None)), st

    def pad_full_cache(st, stacked):
        """Grow full (non-ring) KV caches to pad_cache_to slots."""
        if not pad_cache_to or cfg.attn_window:
            return st
        kv_dim = 3 if stacked else 2

        def padk(c):
            if c.ndim == kv_dim + 2 and c.shape[kv_dim] < pad_cache_to:
                width = [(0, 0)] * c.ndim
                width[kv_dim] = (0, pad_cache_to - c.shape[kv_dim])
                return jnp.pad(c, width)
            return c
        return jax.tree.map(padk, st)

    if "layers_stacked" in params:
        def body(xc, layer_p):
            xn, st = run_layer(layer_p, "attn", xc)
            return xn, st
        x, states = jax.lax.scan(body, x, params["layers_stacked"])
        layers = pad_full_cache(states, stacked=True)
    else:
        layers = []
        for i, layer_p in enumerate(params["layers"]):
            x, st = run_layer(layer_p, pattern[i], x)
            if pattern[i] == "attn" and not cfg.attn_window:
                st = pad_full_cache(st, stacked=False)
            layers.append(st)
    x = apply_norm(cfg, params["final_norm"], x[:, -1:, :])
    logits = lm_logits(params["head"], params["embed"], cfg, x)[:, 0, :]
    return logits, {"pos": jnp.asarray(s, jnp.int32), "layers": layers}


def _mlstm_final_state(p, cfg, x):
    st = ssm_lib.mlstm_init_state(cfg, x.shape[0])
    # replay through step form via scan to obtain the carry

    def step(carry, x_t):
        _, new = ssm_lib.mlstm_step(p, cfg, x_t, carry)
        return new, None
    st, _ = jax.lax.scan(step, st, jnp.moveaxis(x, 1, 0))
    return st


def _slstm_final_state(p, cfg, x):
    st = ssm_lib.slstm_init_state(cfg, x.shape[0], x.shape[-1])

    def step(carry, x_t):
        _, new = ssm_lib.slstm_step(p, cfg, x_t, carry)
        return new, None
    st, _ = jax.lax.scan(step, st, jnp.moveaxis(x, 1, 0))
    return st
