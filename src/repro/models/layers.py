"""Shared layers: norms, RoPE (incl. partial + M-RoPE), MLPs, embeddings."""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, YarnConfig
from repro.distributed.sharding import ShardCtx, NULL_CTX
from repro.models.params import ParamDef, dense

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_defs(cfg: ModelConfig, d: Optional[int] = None) -> Params:
    d = d or cfg.d_model
    out = {"scale": ParamDef((d,), ("embed",), "ones")}
    if cfg.norm == "layernorm":
        out["bias"] = ParamDef((d,), ("embed",), "zeros")
    return out


def apply_norm(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        var = jnp.mean(jnp.square(xf), -1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + cfg.norm_eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_tables(positions: jax.Array, rot_dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables. positions [..., S] -> cos/sin [..., S, rot_dim//2]."""
    half = rot_dim // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.cos(ang), jnp.sin(ang)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_softmax_scale(y: YarnConfig) -> float:
    """What YaRN multiplies the attention's 1/sqrt(d) by (DeepSeek-V2)."""
    return yarn_mscale(y.factor, y.mscale_all_dim) ** 2 if y.mscale_all_dim else 1.0


def yarn_ramp(y: YarnConfig, rot_dim: int, theta: float) -> Tuple[float, float]:
    """The frequency dimensions between which YaRN blends the interpolated
    frequencies into the original ones: below ``low`` a dimension keeps its
    frequency, above ``high`` it is divided by ``factor``."""
    def dim(rotations):
        return (rot_dim * math.log(y.original_max_position_embeddings
                                   / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(dim(y.beta_fast)), 0)
    high = min(math.ceil(dim(y.beta_slow)), rot_dim - 1)
    return low, high


def yarn_tables(positions: jax.Array, rot_dim: int, theta: float,
                y: YarnConfig) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables of YaRN rope (``DeepseekV2YarnRotaryEmbedding``), at
    every position, laid out as ``rope_tables``'."""
    half = rot_dim // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    low, high = yarn_ramp(y, rot_dim, theta)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low if high != low else 0.001), 0.0, 1.0)
    freq = freq / y.factor * ramp + freq * (1.0 - ramp)
    ang = positions[..., None].astype(jnp.float32) * freq
    m = yarn_mscale(y.factor, y.mscale) / yarn_mscale(y.factor, y.mscale_all_dim)
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def mrope_tables(positions: jax.Array, sections: Tuple[int, ...], rot_dim: int,
                 theta: float) -> Tuple[jax.Array, jax.Array]:
    """M-RoPE (qwen2-vl): positions [B, 3, S]; frequency dims split into
    t/h/w sections; each section indexed by its own position row."""
    half = rot_dim // 2
    assert sum(sections) == half, (sections, half)
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang_all = positions[..., None].astype(jnp.float32) * freq  # [B, 3, S, half]
    parts, start = [], 0
    for i, sec in enumerate(sections):
        parts.append(ang_all[:, i, :, start:start + sec])
        start += sec
    ang = jnp.concatenate(parts, axis=-1)  # [B, S, half]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rotary(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """NeoX half-split rotation over the first ``2*cos.shape[-1]`` dims of x.

    x: [B, S, H, D]; cos/sin: [B, S, half] or [S, half]."""
    rot = 2 * cos.shape[-1]
    if cos.ndim == 2:
        cos = cos[None]
        sin = sin[None]
    cos = cos[:, :, None, :]  # [B, S, 1, half]
    sin = sin[:, :, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., : rot // 2], xr[..., rot // 2:]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.concatenate([out1.astype(x.dtype), out2.astype(x.dtype), xp], axis=-1)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_defs(cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.act == "silu":  # SwiGLU
        return {"wi_gate": dense(d, f, ("embed", "ff")),
                "wi_up": dense(d, f, ("embed", "ff")),
                "wo": dense(f, d, ("ff", "embed"))}
    return {"wi": dense(d, f, ("embed", "ff")),
            "wo": dense(f, d, ("ff", "embed"))}


def apply_mlp(cfg: ModelConfig, p: Params, x: jax.Array,
              ctx: ShardCtx = NULL_CTX) -> jax.Array:
    dt = x.dtype
    if cfg.act == "silu":
        h = jax.nn.silu(x @ p["wi_gate"].astype(dt)) * (x @ p["wi_up"].astype(dt))
    else:
        h = jax.nn.gelu(x @ p["wi"].astype(dt))
    h = ctx.constrain(h, ("batch", "seq", "act_ff"))
    return h @ p["wo"].astype(dt)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed_defs(cfg: ModelConfig) -> Params:
    out = {"embedding": ParamDef((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                                 "normal", cfg.d_model ** -0.5)}
    if not cfg.tie_embeddings:
        out["unembed"] = dense(cfg.d_model, cfg.vocab_size, ("embed", "vocab"))
    return out


def embed_tokens(cfg: ModelConfig, p: Params, tokens: jax.Array,
                 ctx: ShardCtx = NULL_CTX) -> jax.Array:
    x = jnp.take(p["embedding"], tokens, axis=0).astype(jnp.dtype(cfg.dtype))
    return ctx.constrain(x, ("batch", "seq", None))


def unembed_matrix(cfg: ModelConfig, p: Params) -> jax.Array:
    return (p["embedding"].T if cfg.tie_embeddings else p["unembed"])
