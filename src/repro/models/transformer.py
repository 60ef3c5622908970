"""Decoder-only LM assembled from blocks (attn / moe / mlstm / slstm / rglru).

Uniform architectures scan over stacked layer params (HLO compression — one
layer body compiled once regardless of depth); heterogeneous patterns unroll.
Decode carries a per-layer state pytree (KV cache / ring window / recurrent
state) with static shapes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (apply_mlp, apply_norm, apply_rope, dtype_of,
                                 embed_tokens, init_embedding, init_lm_head,
                                 init_mlp, init_norm, lm_logits,
                                 sinusoidal_positions)
from repro.parallel import sharding as shd


# ================================================================ init
def init_attn_weights(key, cfg: ModelConfig, d: int):
    ks = jax.random.split(key, 6)
    dt = dtype_of(cfg)
    s = d ** -0.5
    so = cfg.q_dim ** -0.5
    p = {
        "wq": (jax.random.normal(ks[0], (d, cfg.q_dim)) * s).astype(dt),
        "wk": (jax.random.normal(ks[1], (d, cfg.kv_dim)) * s).astype(dt),
        "wv": (jax.random.normal(ks[2], (d, cfg.kv_dim)) * s).astype(dt),
        "wo": (jax.random.normal(ks[3], (cfg.q_dim, d)) * so).astype(dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), jnp.float32)
        p["bk"] = jnp.zeros((cfg.kv_dim,), jnp.float32)
        p["bv"] = jnp.zeros((cfg.kv_dim,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.head_dim,), jnp.float32)
        p["k_norm"] = jnp.ones((cfg.head_dim,), jnp.float32)
    return p


def init_layer(key, cfg: ModelConfig, kind: str):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    if kind == "mlstm":
        return {"kind_mlstm": ssm_lib.init_mlstm(k1, cfg, cfg.d_model)}
    if kind == "slstm":
        return {"kind_slstm": ssm_lib.init_slstm(k1, cfg, cfg.d_model)}
    p = {"ln2": init_norm(cfg, cfg.d_model)}
    if kind == "attn":
        p["ln1"] = init_norm(cfg, cfg.d_model)
        p["attn"] = init_attn_weights(k1, cfg, cfg.d_model)
    elif kind == "rglru":
        p["rec"] = rglru_lib.init_rglru(k1, cfg, cfg.d_model)  # owns its norm
    else:
        raise ValueError(kind)
    if cfg.d_ff:
        if cfg.moe and kind == "attn":
            p["moe"] = moe_lib.init_moe(k2, cfg, cfg.d_model)
        else:
            p["mlp"] = init_mlp(k2, cfg, cfg.d_model, cfg.d_ff)
    return p


def init_params(key, cfg: ModelConfig):
    ke, kh, kl, kf = jax.random.split(key, 4)
    pattern = cfg.pattern()
    params = {"embed": init_embedding(ke, cfg),
              "final_norm": init_norm(cfg, cfg.d_model),
              "head": init_lm_head(kh, cfg)}
    if cfg.scan_layers and len(set(pattern)) == 1 and pattern[0] == "attn":
        keys = jax.random.split(kl, cfg.num_layers)
        params["layers_stacked"] = jax.vmap(
            lambda k: init_layer(k, cfg, "attn"))(keys)
    else:
        keys = jax.random.split(kl, cfg.num_layers)
        params["layers"] = [init_layer(keys[i], cfg, pattern[i])
                            for i in range(cfg.num_layers)]
    return params


# ================================================================ blocks
def _project_qkv(p, cfg: ModelConfig, x, positions):
    """x: (B,S,d) -> q (B,Hq,S,hd), k, v (B,Hkv,S,hd) with rope + qk_norm."""
    b, s, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = (q.astype(jnp.float32) + p["bq"]).astype(x.dtype)
        k = (k.astype(jnp.float32) + p["bk"]).astype(x.dtype)
        v = (v.astype(jnp.float32) + p["bv"]).astype(x.dtype)
    q = q.reshape(b, s, cfg.num_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    if cfg.qk_norm:
        q = _rms_head(q, p["q_norm"], cfg.norm_eps)
        k = _rms_head(k, p["k_norm"], cfg.norm_eps)
    if cfg.rotary_pct > 0:
        q = apply_rope(q, positions[None, None, :], cfg)
        k = apply_rope(k, positions[None, None, :], cfg)
    q = shd.constrain(q, ("batch", "model", None, None))
    k = shd.constrain(k, ("batch", None, None, None))
    v = shd.constrain(v, ("batch", None, None, None))
    return q, k, v


def _rms_head(x, scale, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def attn_block_full(p, cfg: ModelConfig, x, positions):
    h = apply_norm(cfg, p["ln1"], x)
    q, k, v = _project_qkv(p["attn"], cfg, h, positions)
    o = attn_lib.chunked_attention(
        q, k, v, causal=True, window=cfg.attn_window,
        q_positions=positions, kv_positions=positions,
        softcap=cfg.attn_logit_softcap)
    b, hq, s, hd = o.shape
    o = o.transpose(0, 2, 1, 3).reshape(b, s, hq * hd)
    return x + o @ p["attn"]["wo"]


def ffn_block(p, cfg: ModelConfig, x, mesh):
    h = apply_norm(cfg, p["ln2"], x)
    if "moe" in p:
        y, aux = moe_lib.apply_moe(p["moe"], cfg, h, mesh=mesh)
    else:
        y, aux = apply_mlp(p["mlp"], cfg, h), jnp.zeros((), jnp.float32)
    return x + y, aux


def apply_layer_full(p, cfg: ModelConfig, kind: str, x, positions, mesh):
    """One layer, full-sequence. Returns (x, aux)."""
    if kind == "mlstm":
        return ssm_lib.mlstm_scan(p["kind_mlstm"], cfg, x), jnp.zeros(())
    if kind == "slstm":
        return ssm_lib.slstm_scan(p["kind_slstm"], cfg, x), jnp.zeros(())
    if kind == "attn":
        x = attn_block_full(p, cfg, x, positions)
    elif kind == "rglru":
        x = rglru_lib.rglru_forward(p["rec"], cfg, x)  # block owns its norm
    if cfg.d_ff:
        x, aux = ffn_block(p, cfg, x, mesh)
    else:
        aux = jnp.zeros(())
    return x, aux


# ================================================================ forward
def _remat(fn, cfg: ModelConfig):
    mode = cfg.parallel.remat
    if mode == "none":
        return fn
    if mode == "dots_saveable":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_saveable)
    return jax.checkpoint(fn)


def forward(params, cfg: ModelConfig, tokens, *, extra_embeds=None, mesh=None,
            return_hidden=False):
    """tokens: (B, S_text) int32; extra_embeds: (B, P, d) prepended (vlm stub).
    Returns (logits (B,S,V) in bf16, aux_loss scalar); with return_hidden=True
    the first element is the final hidden state (B,S,d) instead (vocab-parallel
    CE computes the logits shard-locally — see DESIGN.md §3)."""
    x = embed_tokens(params["embed"], tokens)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    b, s, d = x.shape
    positions = jnp.arange(s)
    if cfg.rotary_pct == 0:
        x = (x.astype(jnp.float32)
             + sinusoidal_positions(s, d)).astype(x.dtype)
    x = shd.constrain(x, ("batch", None, None))
    aux_total = jnp.zeros((), jnp.float32)

    if "layers_stacked" in params:
        def body(carry, layer_p):
            xc, aux = carry
            xn, a = apply_layer_full(layer_p, cfg, "attn", xc, positions, mesh)
            xn = shd.constrain(xn, ("batch", None, None))
            return (xn, aux + a), None
        body = _remat(body, cfg)
        (x, aux_total), _ = jax.lax.scan(body, (x, aux_total),
                                         params["layers_stacked"])
    else:
        pattern = cfg.pattern()
        for i, layer_p in enumerate(params["layers"]):
            kind = pattern[i]
            # mesh is a static closure, never a traced operand of checkpoint
            fn = _remat(
                lambda x_, pos_, p_=layer_p, k_=kind:
                apply_layer_full(p_, cfg, k_, x_, pos_, mesh), cfg)
            x, a = fn(x, positions)
            aux_total = aux_total + a
    x = apply_norm(cfg, params["final_norm"], x)
    if return_hidden:
        return x, aux_total
    logits = lm_logits(params["head"], params["embed"], cfg, x)
    logits = shd.constrain(logits, ("batch", None, "model"))
    return logits, aux_total


# ================================================================ loss
def cross_entropy(logits, labels, mask=None):
    """Dense CE in f32. logits (B,S,V), labels (B,S)."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    tgt = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = lse - tgt
    if mask is not None:
        nll = nll * mask
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def vocab_parallel_cross_entropy(x, embed_p, head_p, cfg: ModelConfig, labels,
                                 mesh, mask=None):
    """Move-compute CE: per-shard partial max / logsumexp / target-dot over the
    vocab shard; only scalars cross the link (9-byte-response analogue) instead
    of gathering (B,S,V) logits."""
    w = head_p["w"] if not cfg.tie_embeddings else embed_p["table"].T
    baxes = shd.batch_axes(mesh)
    # inside a partial shard_map (Delta-periodic pod loop) the batch is
    # already sliced over the manual axes — the nested shard_map's specs may
    # only mention the still-automatic ones (pmean below still sees all)
    manual = shd.manual_axes()
    spec_b = tuple(a for a in baxes if a not in manual)

    def body(x_, w_, labels_):
        v_loc = w_.shape[1]
        idx = jax.lax.axis_index("model")
        logits = (x_ @ w_).astype(jnp.float32)            # (B,S,Vloc)
        m = jax.lax.pmax(jnp.max(logits, -1), "model")
        lse_loc = jnp.sum(jnp.exp(logits - m[..., None]), -1)
        lse = jnp.log(jax.lax.psum(lse_loc, "model")) + m
        lo = idx * v_loc
        inshard = (labels_ >= lo) & (labels_ < lo + v_loc)
        tgt_loc = jnp.where(
            inshard,
            jnp.take_along_axis(
                logits, jnp.clip(labels_ - lo, 0, v_loc - 1)[..., None],
                axis=-1)[..., 0],
            0.0)
        tgt = jax.lax.psum(tgt_loc, "model")
        nll = lse - tgt
        nll = jax.lax.pmean(nll, baxes)
        return jnp.mean(nll)[None]

    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(spec_b, None, None), P(None, "model"), P(spec_b, None)),
        out_specs=P(None), check_vma=False)(x, w, labels)
    return out[0]
