"""Public model API: build_model(cfg) -> ModelAPI with init / loss / prefill /
decode, plus input_specs() producing ShapeDtypeStruct stand-ins for the
multi-pod dry-run (no allocation).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import decode as decode_lib
from repro.models import encdec as encdec_lib
from repro.models import transformer as tfm
from repro.models.layers import dtype_of

AUX_WEIGHT = 0.01  # MoE load-balance loss weight


@dataclasses.dataclass(frozen=True)
class ModelAPI:
    cfg: ModelConfig
    init: Callable
    loss: Callable            # (params, batch, mesh) -> (loss, metrics)
    prefill: Callable         # (params, batch, mesh) -> (logits, state)
    decode_step: Callable     # (params, state, tokens, mesh) -> (logits, state)
    init_decode_state: Callable  # (batch, max_seq) -> state


def _split_batch(cfg: ModelConfig, batch: Dict[str, Any]):
    tokens = batch["tokens"]
    extra = None
    if cfg.family == "vlm":
        extra = batch["patch_embeds"]
    return tokens, extra


def build_model(cfg: ModelConfig) -> ModelAPI:
    if cfg.family == "audio":
        return _build_encdec(cfg)

    def init(key):
        return tfm.init_params(key, cfg)

    def loss(params, batch, mesh=None):
        tokens, extra = _split_batch(cfg, batch)
        n_patch = 0 if extra is None else extra.shape[1]
        if cfg.parallel.ce_mode == "vocab_parallel" and mesh is not None \
                and mesh.shape.get("model", 1) > 1 \
                and cfg.parallel.layout == "tp":
            hidden, aux = tfm.forward(params, cfg, tokens, extra_embeds=extra,
                                      mesh=mesh, return_hidden=True)
            h = hidden[:, n_patch:-1, :]
            ce = tfm.vocab_parallel_cross_entropy(
                h, params["embed"], params["head"], cfg, tokens[:, 1:], mesh)
        else:
            logits, aux = tfm.forward(params, cfg, tokens, extra_embeds=extra,
                                      mesh=mesh)
            ce = tfm.cross_entropy(logits[:, n_patch:-1, :], tokens[:, 1:])
        total = ce + AUX_WEIGHT * aux
        return total, {"ce": ce, "aux": aux}

    def prefill(params, batch, mesh=None, pad_cache_to=0):
        tokens, extra = _split_batch(cfg, batch)
        return decode_lib.prefill(params, cfg, tokens, extra_embeds=extra,
                                  mesh=mesh, pad_cache_to=pad_cache_to)

    def dstep(params, state, tokens, mesh=None):
        return decode_lib.decode_step(params, cfg, state, tokens, mesh=mesh)

    def dstate(batch, max_seq):
        return decode_lib.init_decode_state(cfg, batch, max_seq)

    return ModelAPI(cfg, init, loss, prefill, dstep, dstate)


def _build_encdec(cfg: ModelConfig) -> ModelAPI:
    def init(key):
        return encdec_lib.init_params(key, cfg)

    def loss(params, batch, mesh=None):
        logits, aux = encdec_lib.forward(params, cfg, batch["frames"],
                                         batch["tokens"], mesh=mesh)
        ce = tfm.cross_entropy(logits[:, :-1, :], batch["tokens"][:, 1:])
        return ce, {"ce": ce, "aux": aux}

    def prefill(params, batch, mesh=None, pad_cache_to=0):
        return encdec_lib.prefill(params, cfg, batch["frames"],
                                  batch["tokens"], mesh=mesh,
                                  pad_cache_to=pad_cache_to)

    def dstep(params, state, tokens, mesh=None):
        return encdec_lib.decode_step(params, cfg, state, tokens, mesh=mesh)

    def dstate(batch, max_seq):
        return encdec_lib.init_decode_state(None, cfg, batch, max_seq)

    return ModelAPI(cfg, init, loss, prefill, dstep, dstate)


# ================================================================ input specs
def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of the entry point
    implied by shape.kind ('train'/'prefill' -> batch dict; 'decode' -> the
    token batch; decode state comes from eval_shape of init_decode_state)."""
    b, s = shape.global_batch, shape.seq_len
    dt = dtype_of(cfg)
    i32 = jnp.int32
    sd = jax.ShapeDtypeStruct
    if shape.kind in ("train", "prefill"):
        if cfg.family == "vlm":
            p = cfg.num_patches
            return {"tokens": sd((b, s - p), i32),
                    "patch_embeds": sd((b, p, cfg.d_model), dt)}
        if cfg.family == "audio":
            return {"frames": sd((b, cfg.encoder_seq, cfg.d_model), dt),
                    "tokens": sd((b, s), i32)}
        return {"tokens": sd((b, s), i32)}
    # decode: one new token against a seq_len-deep state
    return {"tokens": sd((b,), i32)}


def decode_state_specs(cfg: ModelConfig, shape: ShapeConfig):
    """Abstract decode state (no allocation) via eval_shape."""
    api = build_model(cfg)
    return jax.eval_shape(
        lambda: api.init_decode_state(shape.global_batch, shape.seq_len))


def param_specs(cfg: ModelConfig):
    api = build_model(cfg)
    return jax.eval_shape(lambda: api.init(jax.random.key(0)))
