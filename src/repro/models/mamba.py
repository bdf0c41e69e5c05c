"""Mamba-1 selective SSM block (Jamba's mixer).

TPU adaptation: the CUDA "hardware-aware" fused scan becomes a *chunked*
linear-recurrence — ``lax.scan`` over sequence chunks carrying the SSM state,
with a parallel ``associative_scan`` inside each chunk. Only one chunk's
[B, chunk, d_inner, d_state] tensor is live at a time (VMEM/HBM friendly),
and compile time is O(1) in sequence length.

Decode is the exact single-step recurrence with a (conv, ssm) state cache —
the cheapest CSP payload in the framework (O(1) in context length).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ShardCtx, NULL_CTX
from repro.models.params import ParamDef, dense

Params = Dict[str, Any]


def _dims(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    mc = cfg.mamba
    d_in = mc.expand * cfg.d_model
    dt_rank = mc.dt_rank or -(-cfg.d_model // 16)
    return d_in, mc.d_state, mc.d_conv, dt_rank


def mamba_defs(cfg: ModelConfig) -> Params:
    d = cfg.d_model
    d_in, d_state, d_conv, dt_rank = _dims(cfg)
    return {
        "in_proj": dense(d, 2 * d_in, ("embed", "mamba_inner")),
        "conv_w": ParamDef((d_conv, d_in), (None, "mamba_inner"), "normal", d_conv ** -0.5),
        "conv_b": ParamDef((d_in,), ("mamba_inner",), "zeros"),
        "x_proj": dense(d_in, dt_rank + 2 * d_state, ("mamba_inner", None)),
        "dt_proj": dense(dt_rank, d_in, (None, "mamba_inner")),
        "dt_bias": ParamDef((d_in,), ("mamba_inner",), "zeros"),
        "A_log": ParamDef((d_in, d_state), ("mamba_inner", None), "ones"),
        "D": ParamDef((d_in,), ("mamba_inner",), "ones"),
        "out_proj": dense(d_in, d, ("mamba_inner", "embed")),
    }


def mamba_cache_shapes(cfg: ModelConfig, batch: int) -> Dict[str, Tuple[int, ...]]:
    d_in, d_state, d_conv, _ = _dims(cfg)
    return {"conv": (batch, d_conv - 1, d_in), "ssm": (batch, d_in, d_state)}


def _ssm_params(cfg: ModelConfig, p: Params, xc: jax.Array):
    """xc [B, S, d_in] (post-conv, post-silu) -> (A_bar, Bx) for the recurrence."""
    d_in, d_state, _, dt_rank = _dims(cfg)
    dt = xc.dtype
    proj = xc @ p["x_proj"].astype(dt)                      # [B,S,r+2n]
    delta_r = proj[..., :dt_rank]
    B_ssm = proj[..., dt_rank:dt_rank + d_state].astype(jnp.float32)
    C_ssm = proj[..., dt_rank + d_state:].astype(jnp.float32)
    delta = jax.nn.softplus((delta_r @ p["dt_proj"].astype(dt)).astype(jnp.float32)
                            + p["dt_bias"].astype(jnp.float32))  # [B,S,d_in]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))            # [d_in, n]
    A_bar = jnp.exp(delta[..., None] * A)                   # [B,S,d_in,n]
    Bx = (delta * xc.astype(jnp.float32))[..., None] * B_ssm[:, :, None, :]
    return A_bar, Bx, C_ssm


def _chunk_scan(A_bar, Bx, h0):
    """Linear recurrence h_t = A_t h_{t-1} + b_t within one chunk.

    A_bar/Bx: [B, L, d_in, n]; h0: [B, d_in, n] (fp32). Returns (h_all, h_last)."""
    def op(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a2 * a1, a2 * b1 + b2
    a_cum, b_cum = jax.lax.associative_scan(op, (A_bar, Bx), axis=1)
    h_all = b_cum + a_cum * h0[:, None]                     # [B,L,d_in,n]
    return h_all, h_all[:, -1]


def _causal_conv(cfg, p, x, conv_state=None):
    """Depthwise causal conv over seq. x [B,S,d_in]; state [B, d_conv-1, d_in]."""
    d_in, _, d_conv, _ = _dims(cfg)
    dt = x.dtype
    if conv_state is None:
        pad = jnp.zeros((x.shape[0], d_conv - 1, d_in), dt)
    else:
        pad = conv_state.astype(dt)
    xp = jnp.concatenate([pad, x], axis=1)                  # [B, S+dc-1, d_in]
    w = p["conv_w"].astype(dt)                              # [d_conv, d_in]
    out = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(d_conv))
    new_state = xp[:, -(d_conv - 1):, :] if d_conv > 1 else pad
    return jax.nn.silu(out + p["conv_b"].astype(dt)), new_state


def mamba_apply(cfg: ModelConfig, p: Params, x: jax.Array, *,
                mode: str, ctx: ShardCtx = NULL_CTX,
                cache: Optional[Params] = None,
                ) -> Tuple[jax.Array, Optional[Params]]:
    """x [B,S,D]. mode train/prefill: chunked scan (prefill returns final
    state cache); mode decode: S==1 exact recurrence against the cache."""
    mc = cfg.mamba
    d_in, d_state, d_conv, _ = _dims(cfg)
    dt = x.dtype
    B, S, _ = x.shape

    xz = x @ p["in_proj"].astype(dt)
    xin, z = xz[..., :d_in], xz[..., d_in:]
    xin = ctx.constrain(xin, ("batch", "seq", "act_heads"))

    if mode == "decode":
        xc, new_conv = _causal_conv(cfg, p, xin, cache["conv"])
        A_bar, Bx, C_ssm = _ssm_params(cfg, p, xc)
        h = A_bar[:, 0] * cache["ssm"].astype(jnp.float32) + Bx[:, 0]  # [B,d_in,n]
        y = jnp.einsum("bdn,bn->bd", h, C_ssm[:, 0])[:, None, :]       # [B,1,d_in]
        new_cache = {"conv": new_conv.astype(cache["conv"].dtype), "ssm": h}
    else:
        xc, last_conv = _causal_conv(cfg, p, xin)
        L = min(mc.chunk, S)
        while S % L:          # largest divisor <= chunk (exact state carry)
            L -= 1
        nchunk = S // L

        def rs(t):  # [B,S,...] -> [nchunk, B, L, ...]
            return jnp.moveaxis(t.reshape(B, nchunk, L, *t.shape[2:]), 1, 0)

        h0 = jnp.zeros((B, d_in, d_state), jnp.float32)
        if mc.perchunk_params:
            def step(h, xc_chunk):
                # §Perf: SSM params (A_bar/Bx, fp32, [B,L,d_in,n]) computed
                # PER CHUNK — materializing them for the full sequence was
                # the memory-term dominator (2 x 34 GiB/device at train_4k).
                a, b, c = _ssm_params(cfg, p, xc_chunk)
                h_all, h_last = _chunk_scan(a, b, h)
                yc = jnp.einsum("bldn,bln->bld", h_all, c)
                return h_last, yc.astype(xc_chunk.dtype)
            xs = rs(xc)
        else:
            def step(h, inp):  # paper-naive: full-sequence A_bar/Bx inputs
                a, b, c = inp
                h_all, h_last = _chunk_scan(a, b, h)
                yc = jnp.einsum("bldn,bln->bld", h_all, c)
                return h_last, yc.astype(xc.dtype)
            A_bar, Bx, C_full = _ssm_params(cfg, p, xc)
            xs = (rs(A_bar), rs(Bx), rs(C_full))
        h_last, ys = jax.lax.scan(step, h0, xs)
        y = jnp.moveaxis(ys, 0, 1).reshape(B, S, d_in)
        new_cache = None
        if mode == "prefill":
            new_cache = {"conv": last_conv, "ssm": h_last}

    y = (y.astype(dt) + xc * p["D"].astype(dt)) * jax.nn.silu(z)
    y = ctx.constrain(y, ("batch", "seq", "act_heads"))
    return y @ p["out_proj"].astype(dt), new_cache
