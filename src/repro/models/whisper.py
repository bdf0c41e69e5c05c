"""Whisper-style encoder-decoder backbone (whisper-medium).

The audio frontend (log-mel + 2x conv1d) is a STUB per the assignment:
``input_specs()`` supplies precomputed frame embeddings [B, n_frames, d_model].
Encoder: non-causal self-attn + GELU MLP, sinusoidal positions.
Decoder: causal self-attn (KV cache) + cross-attn (encoder K/V cached at
prefill) + GELU MLP, learned positions. Embeddings tied.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ShardCtx, NULL_CTX
from repro.models import attention as attn
from repro.models import layers as L
from repro.models.params import ParamDef, init_params, stack_defs

Params = Dict[str, Any]

MAX_DEC_POS = 32_768  # covers the assigned decode shapes


def _sinusoid(n: int, d: int) -> jax.Array:
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / (10_000 ** (dim / (d // 2 - 1)))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _enc_block_defs(cfg: ModelConfig) -> Params:
    return {"attn_norm": L.norm_defs(cfg), "attn": attn.attn_defs(cfg),
            "mlp_norm": L.norm_defs(cfg), "mlp": L.mlp_defs(cfg)}


def _dec_block_defs(cfg: ModelConfig) -> Params:
    return {"self_norm": L.norm_defs(cfg), "self_attn": attn.attn_defs(cfg),
            "cross_norm": L.norm_defs(cfg), "cross_attn": attn.attn_defs(cfg),
            "mlp_norm": L.norm_defs(cfg), "mlp": L.mlp_defs(cfg)}


def whisper_defs(cfg: ModelConfig) -> Params:
    enc = cfg.encoder
    return {
        "embed": L.embed_defs(cfg),
        "dec_pos": ParamDef((MAX_DEC_POS, cfg.d_model), (None, "embed"),
                            "normal", 0.01),
        "enc_blocks": stack_defs(_enc_block_defs(cfg), enc.num_layers, "layers"),
        "enc_norm": L.norm_defs(cfg),
        "dec_blocks": stack_defs(_dec_block_defs(cfg), cfg.num_layers, "layers"),
        "final_norm": L.norm_defs(cfg),
    }


def init(cfg: ModelConfig, key: jax.Array) -> Params:
    return init_params(whisper_defs(cfg), key, cfg.param_dtype)


# ---------------------------------------------------------------------------

def encode(cfg: ModelConfig, params: Params, frames: jax.Array,
           ctx: ShardCtx = NULL_CTX) -> jax.Array:
    """frames [B, S_enc, D] (stub embeddings) -> encoder states."""
    B, S, D = frames.shape
    x = frames.astype(jnp.dtype(cfg.dtype)) + _sinusoid(S, D).astype(cfg.dtype)[None]
    x = ctx.constrain(x, ("batch", "seq", None))

    def body(x, p):
        h = L.apply_norm(cfg, p["attn_norm"], x)
        y, _ = attn.gqa_apply(cfg, p["attn"], h, rope=None, mode="train",
                              ctx=ctx, causal=False)
        x = x + y
        h = L.apply_norm(cfg, p["mlp_norm"], x)
        x = x + L.apply_mlp(cfg, p["mlp"], h, ctx)
        return ctx.constrain(x, ("batch", "seq", None)), {}

    x, _ = jax.lax.scan(body, x, params["enc_blocks"])
    return L.apply_norm(cfg, params["enc_norm"], x)


def _cross_kv(cfg: ModelConfig, p: Params, enc_out: jax.Array):
    dt = enc_out.dtype
    B, S, _ = enc_out.shape
    hd, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
    k = enc_out @ p["wk"].astype(dt)
    v = enc_out @ p["wv"].astype(dt)
    if "bk" in p:
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    return k.reshape(B, S, nkv, hd), v.reshape(B, S, nkv, hd)


def _decode_stack(cfg: ModelConfig, params: Params, x: jax.Array, *, mode: str,
                  ctx: ShardCtx, enc_out: Optional[jax.Array],
                  self_cache, cross_cache, pos):
    """Runs decoder blocks via scan. cross_cache: {"k","v"} [Ld,B,Se,H,hd] or
    None (computed from enc_out on the fly)."""
    has_self = self_cache is not None
    has_cross = cross_cache is not None

    def body(x, per_layer):
        p, sc, cc = per_layer
        h = L.apply_norm(cfg, p["self_norm"], x)
        y, new_sc = attn.gqa_apply(cfg, p["self_attn"], h, rope=None, mode=mode,
                                   ctx=ctx, cache=sc if has_self else None, pos=pos)
        x = x + y
        h = L.apply_norm(cfg, p["cross_norm"], x)
        if has_cross:
            kv = (cc["k"].astype(x.dtype), cc["v"].astype(x.dtype))
        else:
            kv = _cross_kv(cfg, p["cross_attn"], enc_out)
        y, _ = attn.gqa_apply(cfg, p["cross_attn"], h, rope=None, mode="train",
                              ctx=ctx, kv_override=kv)
        x = x + y
        h = L.apply_norm(cfg, p["mlp_norm"], x)
        x = x + L.apply_mlp(cfg, p["mlp"], h, ctx)
        x = ctx.constrain(x, ("batch", "seq", None))
        ys = {}
        if mode == "prefill":
            ys = {"self": new_sc, "cross": {"k": kv[0], "v": kv[1]}}
        elif mode == "decode":
            ys = {"self": new_sc}
        return x, ys

    sc = self_cache if self_cache is not None else \
        jax.tree.map(lambda _: None, params["dec_blocks"])
    cc = cross_cache if cross_cache is not None else \
        jax.tree.map(lambda _: None, params["dec_blocks"])
    x, ys = jax.lax.scan(body, x, (params["dec_blocks"], sc, cc))
    return x, ys


def _dec_embed(cfg, params, tokens, pos, ctx):
    B, S = tokens.shape
    x = L.embed_tokens(cfg, params["embed"], tokens, ctx)
    if pos is None:
        pe = jax.lax.dynamic_slice_in_dim(params["dec_pos"], 0, S, 0)
    else:
        pe = jax.lax.dynamic_slice_in_dim(params["dec_pos"], pos, S, 0)
    return x + pe.astype(x.dtype)[None]


def loss_fn(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
            ctx: ShardCtx = NULL_CTX):
    from repro.models import lm  # chunked_xent
    enc_out = encode(cfg, params, batch["frames"], ctx)
    x = _dec_embed(cfg, params, batch["tokens"], None, ctx)
    x, _ = _decode_stack(cfg, params, x, mode="train", ctx=ctx, enc_out=enc_out,
                         self_cache=None, cross_cache=None, pos=None)
    x = L.apply_norm(cfg, params["final_norm"], x)
    loss = lm.chunked_xent(cfg, params, x, batch["labels"], ctx)
    return loss, {"loss": loss, "xent": loss}


def prefill(cfg: ModelConfig, params: Params, frames: jax.Array,
            tokens: jax.Array, ctx: ShardCtx = NULL_CTX):
    from repro.models import lm
    enc_out = encode(cfg, params, frames, ctx)
    x = _dec_embed(cfg, params, tokens, None, ctx)
    x, cache = _decode_stack(cfg, params, x, mode="prefill", ctx=ctx,
                             enc_out=enc_out, self_cache=None, cross_cache=None,
                             pos=None)
    x = L.apply_norm(cfg, params["final_norm"], x)
    return lm.logits_at_last(cfg, params, x, ctx), cache


def decode_step(cfg: ModelConfig, params: Params, cache: Params,
                token: jax.Array, pos: jax.Array, ctx: ShardCtx = NULL_CTX):
    from repro.models import lm
    x = _dec_embed(cfg, params, token, pos, ctx)
    x, ys = _decode_stack(cfg, params, x, mode="decode", ctx=ctx,
                          enc_out=None, self_cache=cache["self"],
                          cross_cache=cache["cross"], pos=pos)
    x = L.apply_norm(cfg, params["final_norm"], x)
    new_cache = {"self": ys["self"], "cross": cache["cross"]}
    return lm.logits_at_last(cfg, params, x, ctx), new_cache


def cache_sds(cfg: ModelConfig, batch: int, max_len: int):
    """Abstract decode cache (self KV at max_len + cross KV at n_frames)."""
    hd, nkv, Ld = cfg.resolved_head_dim, cfg.num_kv_heads, cfg.num_layers
    Se = cfg.encoder.num_frames
    dt = jnp.dtype(cfg.dtype)
    sds = jax.ShapeDtypeStruct
    return {
        "self": {"k": sds((Ld, batch, max_len, nkv, hd), dt),
                 "v": sds((Ld, batch, max_len, nkv, hd), dt)},
        "cross": {"k": sds((Ld, batch, Se, nkv, hd), dt),
                  "v": sds((Ld, batch, Se, nkv, hd), dt)},
    }


def cache_axes_tree():
    ax = ("layers", "cache_batch", "cache_seq", "cache_heads", None)
    axe = ("layers", "cache_batch", None, "cache_heads", None)
    return {"self": {"k": ax, "v": ax}, "cross": {"k": axe, "v": axe}}
