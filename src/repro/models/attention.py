"""Attention: GQA (glm4/qwen3/stablelm/jamba/olmoe/qwen2-moe/whisper/vlm)
and MLA (minicpm3, DeepSeek-V2-style latent KV with absorbed decode).

Cache layout (per scanned layer-stack slot):
  GQA : {"k": [B, S_max, H_kv, hd], "v": [...]}        axes (cache_batch, cache_seq, cache_heads, None)
  MLA : {"ckv": [B, S_max, r], "kpe": [B, S_max, dr]}  axes (cache_batch, cache_seq, None)
The fill position ``pos`` (scalar int32) is carried outside the layer stack.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ShardCtx, NULL_CTX
from repro.models.params import ParamDef, dense
from repro.models.layers import apply_rotary, rms_norm, yarn_softmax_scale

Params = Dict[str, Any]
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Defs
# ---------------------------------------------------------------------------

def attn_defs(cfg: ModelConfig, cross: bool = False) -> Params:
    if cfg.attention_type == "mla" and not cross:
        return _mla_defs(cfg)
    return _gqa_defs(cfg, cross=cross)


def _gqa_defs(cfg: ModelConfig, cross: bool = False) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    out: Params = {
        "wq": dense(d, nq * hd, ("embed", "heads")),
        "wk": dense(d, nkv * hd, ("embed", "kv_heads")),
        "wv": dense(d, nkv * hd, ("embed", "kv_heads")),
        "wo": dense(nq * hd, d, ("heads", "embed")),
    }
    if cfg.qkv_bias:
        out["bq"] = ParamDef((nq * hd,), ("heads",), "zeros")
        out["bk"] = ParamDef((nkv * hd,), ("kv_heads",), "zeros")
        out["bv"] = ParamDef((nkv * hd,), ("kv_heads",), "zeros")
    if cfg.qk_norm:
        out["q_norm"] = ParamDef((hd,), (None,), "ones")
        out["k_norm"] = ParamDef((hd,), (None,), "ones")
    return out


def _mla_defs(cfg: ModelConfig) -> Params:
    m, d, nq = cfg.mla, cfg.d_model, cfg.num_heads
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank is None:
        q = {"wq": dense(d, nq * qd, ("embed", "heads"))}
    else:
        q = {"wq_a": dense(d, m.q_lora_rank, ("embed", "lora")),
             "q_norm": ParamDef((m.q_lora_rank,), (None,), "ones"),
             "wq_b": dense(m.q_lora_rank, nq * qd, ("lora", "heads"))}
    return {
        **q,
        "wkv_a": dense(d, m.kv_lora_rank + m.qk_rope_head_dim, ("embed", None)),
        "kv_norm": ParamDef((m.kv_lora_rank,), (None,), "ones"),
        "wkv_b": dense(m.kv_lora_rank,
                       nq * (m.qk_nope_head_dim + m.v_head_dim), ("lora", "heads")),
        "wo": dense(nq * m.v_head_dim, d, ("heads", "embed")),
    }


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    """Per-layer cache entry shapes (the layer stack's axis added by lm)."""
    hd, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
    if cfg.attention_type == "mla":
        m = cfg.mla
        return {"ckv": (batch, max_len, m.kv_lora_rank),
                "kpe": (batch, max_len, m.qk_rope_head_dim)}
    return {"k": (batch, max_len, nkv, hd), "v": (batch, max_len, nkv, hd)}


def cache_axes(cfg: ModelConfig) -> Params:
    """Logical axes of the stacked cache entries ``init_cache`` shapes."""
    if cfg.attention_type == "mla":
        return {"ckv": ("layers", "cache_batch", "cache_seq", None),
                "kpe": ("layers", "cache_batch", "cache_seq", None)}
    ax = ("layers", "cache_batch", "cache_seq", "cache_heads", None)
    return {"k": ax, "v": ax}


# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------

def _sdpa(cfg: ModelConfig, q: jax.Array, k: jax.Array, v: jax.Array,
          mask: jax.Array, ctx: ShardCtx, scale: float) -> jax.Array:
    """q [B,Sq,Hq,hd], k/v [B,Skv,Hkv,hd], mask [B or 1, Sq, Skv] bool."""
    B, Sq, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, hd)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) * scale
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", w, v)
    dv = v.shape[-1]  # may differ from hd (MLA)
    return out.reshape(B, Sq, Hq * dv)


# ---------------------------------------------------------------------------
# GQA apply
# ---------------------------------------------------------------------------

def gqa_apply(cfg: ModelConfig, p: Params, x: jax.Array, *,
              rope: Optional[Tuple[jax.Array, jax.Array]],
              mode: str, ctx: ShardCtx = NULL_CTX,
              cache: Optional[Params] = None, pos: Optional[jax.Array] = None,
              kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
              causal: bool = True,
              ) -> Tuple[jax.Array, Optional[Params]]:
    """mode in {train, prefill, decode}; cross-attention via kv_override
    (pre-projected encoder k/v, no cache update)."""
    dt = x.dtype
    B, S, _ = x.shape
    hd, nq, nkv = cfg.resolved_head_dim, cfg.num_heads, cfg.num_kv_heads

    q = x @ p["wq"].astype(dt)
    if "bq" in p:
        q = q + p["bq"].astype(dt)
    q = q.reshape(B, S, nq, hd)

    if kv_override is None:
        k = x @ p["wk"].astype(dt)
        v = x @ p["wv"].astype(dt)
        if "bk" in p:
            k = k + p["bk"].astype(dt)
            v = v + p["bv"].astype(dt)
        k = k.reshape(B, S, nkv, hd)
        v = v.reshape(B, S, nkv, hd)
    else:
        k, v = kv_override

    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        if kv_override is None:
            k = rms_norm(k, p["k_norm"], cfg.norm_eps)

    if rope is not None and kv_override is None:
        cos, sin = rope
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)

    scale = hd ** -0.5
    new_cache = None

    if mode == "decode" and kv_override is None:
        # insert new k/v at pos, attend over cache[0..pos]
        ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, pos, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, pos, 0, 0))
        new_cache = {"k": ck, "v": cv}
        S_max = ck.shape[1]
        valid = (jnp.arange(S_max) <= pos)[None, None, :]  # [1,1,S_max]
        y = _sdpa(cfg, q, ck.astype(dt), cv.astype(dt), valid, ctx, scale)
    else:
        if mode == "prefill" and kv_override is None:
            new_cache = {"k": k, "v": v}
        if kv_override is not None:  # cross-attention: full visibility
            mask = jnp.ones((1, S, k.shape[1]), bool)
            y = _sdpa(cfg, q, k.astype(dt), v.astype(dt), mask, ctx, scale)
        else:
            mask = (jnp.tril(jnp.ones((S, S), bool)) if causal
                    else jnp.ones((S, S), bool))[None]
            y = _sdpa(cfg, q, k, v, mask, ctx, scale)

    y = ctx.constrain(y, ("batch", "seq", "act_heads"))
    return y @ p["wo"].astype(dt), new_cache


# ---------------------------------------------------------------------------
# MLA apply
# ---------------------------------------------------------------------------

def mla_apply(cfg: ModelConfig, p: Params, x: jax.Array, *,
              rope: Optional[Tuple[jax.Array, jax.Array]],
              mode: str, ctx: ShardCtx = NULL_CTX,
              cache: Optional[Params] = None, pos: Optional[jax.Array] = None,
              ) -> Tuple[jax.Array, Optional[Params]]:
    with jax.named_scope("truffle.mla"):
        return _mla(cfg, p, x, rope=rope, mode=mode, ctx=ctx, cache=cache, pos=pos)


def _mla(cfg: ModelConfig, p: Params, x: jax.Array, *, rope, mode: str,
         ctx: ShardCtx, cache: Optional[Params], pos: Optional[jax.Array]):
    m = cfg.mla
    dt = x.dtype
    B, S, _ = x.shape
    nq = cfg.num_heads
    nope, rdim, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    cos, sin = rope

    if m.q_lora_rank is None:
        q = x @ p["wq"].astype(dt)
    else:
        ql = rms_norm(x @ p["wq_a"].astype(dt), p["q_norm"], cfg.norm_eps)
        q = ql @ p["wq_b"].astype(dt)
    q = q.reshape(B, S, nq, nope + rdim)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    q_pe = apply_rotary(q_pe, cos, sin)

    kv_a = x @ p["wkv_a"].astype(dt)
    ckv = rms_norm(kv_a[..., :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_pe = apply_rotary(kv_a[..., m.kv_lora_rank:][:, :, None, :], cos, sin)[:, :, 0, :]

    scale = (nope + rdim) ** -0.5
    if cfg.yarn is not None:
        scale *= yarn_softmax_scale(cfg.yarn)
    wkv_b = p["wkv_b"].astype(dt).reshape(m.kv_lora_rank, nq, nope + vd)
    w_uk, w_uv = wkv_b[..., :nope], wkv_b[..., nope:]

    new_cache = None
    if mode == "decode":
        cckv = jax.lax.dynamic_update_slice(cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, pos, 0))
        ckpe = jax.lax.dynamic_update_slice(cache["kpe"], k_pe.astype(cache["kpe"].dtype), (0, pos, 0))
        new_cache = {"ckv": cckv, "kpe": ckpe}
        # absorbed decode: scores in latent space (r + rdim per head)
        q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_uk)           # [B,1,H,r]
        scores = (jnp.einsum("bqhr,bsr->bhqs", q_lat, cckv.astype(dt))
                  + jnp.einsum("bqhp,bsp->bhqs", q_pe, ckpe.astype(dt))
                  ).astype(jnp.float32) * scale
        S_max = cckv.shape[1]
        valid = (jnp.arange(S_max) <= pos)[None, None, None, :]
        scores = jnp.where(valid, scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1).astype(dt)
        o_lat = jnp.einsum("bhqs,bsr->bqhr", w, cckv.astype(dt))     # [B,1,H,r]
        y = jnp.einsum("bqhr,rhv->bqhv", o_lat, w_uv).reshape(B, S, nq * vd)
    else:
        kv = jnp.einsum("bsr,rhn->bshn", ckv, jnp.concatenate([w_uk, w_uv], -1))
        k_nope, v = kv[..., :nope], kv[..., nope:]
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, :, None, :], (B, S, nq, rdim))], -1)
        qf = jnp.concatenate([q_nope, q_pe], -1)
        causal = jnp.tril(jnp.ones((S, S), bool))[None]
        y = _sdpa(cfg, qf, k, v, causal, ctx, scale)  # -> [B, S, nq*vd]
        if mode == "prefill":
            new_cache = {"ckv": ckv, "kpe": k_pe}

    y = ctx.constrain(y, ("batch", "seq", "act_heads"))
    return y @ p["wo"].astype(dt), new_cache
