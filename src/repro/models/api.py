"""Unified model API: dispatches lm.py vs whisper.py by family, owns the
decode cache's layout (``cache_sds``, ``cache_axes``, ``grow_cache``), and
builds the abstract ``input_specs`` (ShapeDtypeStructs) a step lowers against
without allocating."""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed.sharding import ShardCtx, NULL_CTX
from repro.models import lm, whisper
from repro.models.params import abstract_params, init_params

Params = Dict[str, Any]


def is_encdec(cfg: ModelConfig) -> bool:
    return cfg.encoder is not None


def model_defs(cfg: ModelConfig):
    return whisper.whisper_defs(cfg) if is_encdec(cfg) else lm.lm_defs(cfg)


def init(cfg: ModelConfig, key: jax.Array) -> Params:
    return init_params(model_defs(cfg), key, cfg.param_dtype)


def abstract(cfg: ModelConfig) -> Params:
    return abstract_params(model_defs(cfg), cfg.param_dtype)


def loss_fn(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
            ctx: ShardCtx = NULL_CTX):
    if is_encdec(cfg):
        return whisper.loss_fn(cfg, params, batch, ctx)
    return lm.loss_fn(cfg, params, batch, ctx)


def prefill(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
            ctx: ShardCtx = NULL_CTX):
    if is_encdec(cfg):
        return whisper.prefill(cfg, params, batch["frames"], batch["tokens"], ctx)
    return lm.prefill(cfg, params, batch["tokens"], ctx=ctx,
                      vision_embeds=batch.get("vision_embeds"),
                      mrope_positions=batch.get("mrope_positions"))


def decode_step(cfg: ModelConfig, params: Params, cache: Params,
                token: jax.Array, pos: jax.Array, ctx: ShardCtx = NULL_CTX,
                counters: bool = False):
    """(logits, cache), and the step's MoE counters after them where
    ``counters`` is set and the model has expert-parallel layers
    (``lm.decode_step``)."""
    if is_encdec(cfg):
        return whisper.decode_step(cfg, params, cache, token, pos, ctx)
    return lm.decode_step(cfg, params, cache, token, pos, ctx=ctx,
                          counters=counters)


def cache_sds(cfg: ModelConfig, batch: int, max_len: int):
    if is_encdec(cfg):
        return whisper.cache_sds(cfg, batch, max_len)
    return lm.cache_sds(cfg, batch, max_len)


def cache_axes(cfg: ModelConfig):
    """Logical-axis tree matching cache_sds structure."""
    if is_encdec(cfg):
        return whisper.cache_axes_tree()
    _, _, axes = lm.cache_spec(cfg, 1, 1)
    return axes


def grow_cache(cfg: ModelConfig, cache, max_len: int):
    """Pad a prefill cache out to ``max_len`` decode slots: every leaf along
    the axis ``cache_axes`` labels ``cache_seq``, and no other leaf or axis
    (recurrent state and whisper's cross k/v pass unchanged)."""
    def pad(a, axes):
        if "cache_seq" not in axes:
            return a
        seq = axes.index("cache_seq")
        extra = max_len - a.shape[seq]
        if extra <= 0:
            return a
        widths = [(0, 0)] * a.ndim
        widths[seq] = (0, extra)
        return jnp.pad(a, widths)

    return jax.tree.map(pad, cache, cache_axes(cfg))


# ---------------------------------------------------------------------------
# Abstract input specs per (arch x shape).
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.dtype(jnp.int32)
    dt = jnp.dtype(cfg.dtype)
    sds = jax.ShapeDtypeStruct

    if shape.kind == "train":
        batch: Dict[str, Any] = {"tokens": sds((B, S), i32), "labels": sds((B, S), i32)}
        if is_encdec(cfg):
            batch["frames"] = sds((B, cfg.encoder.num_frames, cfg.d_model), dt)
        if cfg.vision is not None:
            batch["vision_embeds"] = sds((B, cfg.vision.num_image_tokens, cfg.d_model), dt)
            batch["mrope_positions"] = sds((B, 3, S), i32)
        return {"batch": batch}

    if shape.kind == "prefill":
        batch = {"tokens": sds((B, S), i32)}
        if is_encdec(cfg):
            batch["frames"] = sds((B, cfg.encoder.num_frames, cfg.d_model), dt)
        if cfg.vision is not None:
            batch["vision_embeds"] = sds((B, cfg.vision.num_image_tokens, cfg.d_model), dt)
            batch["mrope_positions"] = sds((B, 3, S), i32)
        return {"batch": batch}

    # decode: one new token against a seq_len cache
    return {
        "cache": cache_sds(cfg, B, S),
        "token": sds((B, 1), i32),
        "pos": sds((), i32),
    }


def concrete_inputs(cfg: ModelConfig, shape: ShapeConfig, seed: int = 0) -> Dict[str, Any]:
    """Small concrete version of input_specs (smoke tests / examples)."""
    key = jax.random.PRNGKey(seed)
    specs = input_specs(cfg, shape)

    def mk(s: jax.ShapeDtypeStruct):
        if jnp.issubdtype(s.dtype, jnp.integer):
            return jax.random.randint(key, s.shape, 0, max(cfg.vocab_size - 1, 2)
                                      ).astype(s.dtype)
        return jax.random.normal(key, s.shape, jnp.float32).astype(s.dtype) * 0.1

    out = jax.tree.map(mk, specs)
    if "pos" in out:
        out["pos"] = jnp.asarray(shape.seq_len - 1, jnp.int32)
        out["cache"] = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                    specs["cache"])
    if "batch" in out and "mrope_positions" in out.get("batch", {}):
        B, _, S = specs["batch"]["mrope_positions"].shape
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, 3, S))
        out["batch"]["mrope_positions"] = pos
    return out
