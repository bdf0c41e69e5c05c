"""xLSTM blocks: mLSTM (matrix memory, chunkwise-parallel) and sLSTM
(scalar memory, strictly recurrent), after arXiv:2405.04517.

TPU adaptation: mLSTM's recurrent form is reorganized into a *chunkwise*
algorithm — intra-chunk attention-like einsums (MXU-friendly matmuls) plus an
inter-chunk carried state (C, n, m), all in stabilized log-space. sLSTM is an
exact ``lax.scan`` recurrence (its memory-mixing recurrence is inherently
sequential; that is the point of the architecture).

Simplifications vs. the reference implementation (noted in DESIGN.md):
the mLSTM block's causal-conv pre-layer and learnable skip are omitted;
output gating uses the block's z-branch (silu) as in the paper's block figure.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ShardCtx, NULL_CTX
from repro.models.params import ParamDef, dense

Params = Dict[str, Any]
MIN_LOG = -1e30


def _mlstm_dims(cfg: ModelConfig) -> Tuple[int, int, int]:
    d_in = int(cfg.xlstm.mlstm_proj_factor * cfg.d_model)
    H = cfg.num_heads
    return d_in, H, d_in // H


def mlstm_defs(cfg: ModelConfig) -> Params:
    d = cfg.d_model
    d_in, H, _ = _mlstm_dims(cfg)
    return {
        "up_proj": dense(d, 2 * d_in, ("embed", "heads")),
        "wq": dense(d_in, d_in, ("heads", None)),
        "wk": dense(d_in, d_in, ("heads", None)),
        "wv": dense(d_in, d_in, ("heads", None)),
        "w_i": dense(d_in, H, (None, None)),
        "b_i": ParamDef((H,), (None,), "zeros"),
        "w_f": dense(d_in, H, (None, None)),
        "b_f": ParamDef((H,), (None,), "ones", scale=3.0),  # long-memory bias
        "mh_norm": ParamDef((d_in,), ("heads",), "ones"),
        "down_proj": dense(d_in, d, ("heads", "embed")),
    }


def mlstm_cache_shapes(cfg: ModelConfig, batch: int) -> Dict[str, Tuple[int, ...]]:
    _, H, dk = _mlstm_dims(cfg)
    return {"C": (batch, H, dk, dk), "n": (batch, H, dk), "m": (batch, H)}


def slstm_defs(cfg: ModelConfig) -> Params:
    d, H = cfg.d_model, cfg.num_heads
    dh = d // H
    return {
        "w_x": dense(d, 4 * d, ("embed", "heads")),
        "b_x": ParamDef((4 * d,), ("heads",), "zeros"),
        "r": ParamDef((4, H, dh, dh), (None, "heads", None, None), "normal", dh ** -0.5),
        "gn": ParamDef((d,), ("embed",), "ones"),
    }


def slstm_cache_shapes(cfg: ModelConfig, batch: int) -> Dict[str, Tuple[int, ...]]:
    d, H = cfg.d_model, cfg.num_heads
    return {"c": (batch, d), "n": (batch, d), "h": (batch, d), "m": (batch, H)}


def _headwise_rms(x: jax.Array, scale: jax.Array, H: int, eps: float) -> jax.Array:
    """x [B,S,d_in] normalized per head (multi-head norm)."""
    B, S, d_in = x.shape
    xh = x.reshape(B, S, H, d_in // H).astype(jnp.float32)
    xh = xh * jax.lax.rsqrt(jnp.mean(jnp.square(xh), -1, keepdims=True) + eps)
    return (xh.reshape(B, S, d_in) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------

def _mlstm_chunk(q, k, v, i_pre, log_f, carry):
    """One chunk, stabilized log-space. q/k/v [B,L,H,dk]; gates [B,L,H].
    carry = (C [B,H,dk,dk], n [B,H,dk], m [B,H]), all fp32."""
    C0, n0, m0 = carry
    B, L, H, dk = q.shape
    qf = q.astype(jnp.float32) * dk ** -0.5
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)

    F = jnp.cumsum(log_f, axis=1)                            # [B,L,H]
    # intra-chunk log decay a[t,s] = F_t - F_s + i_s  (s <= t)
    a = F[:, :, None, :] - F[:, None, :, :] + i_pre[:, None, :, :]
    tri = jnp.tril(jnp.ones((L, L), bool))[None, :, :, None]
    a = jnp.where(tri, a, MIN_LOG)
    b = m0[:, None, :] + F                                   # inter log decay [B,L,H]
    m_t = jnp.maximum(jnp.max(a, axis=2), b)                 # [B,L,H]

    w = jnp.exp(a - m_t[:, :, None, :]) * jnp.einsum("blhd,bshd->blsh", qf, kf)
    num = jnp.einsum("blsh,bshd->blhd", w, vf)
    den = jnp.sum(w, axis=2)                                 # [B,L,H]
    g = jnp.exp(b - m_t)                                     # [B,L,H]
    num = num + g[..., None] * jnp.einsum("blhd,bhde->blhe", qf, C0)
    den = den + g * jnp.einsum("blhd,bhd->blh", qf, n0)
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_t))[..., None]

    # end-of-chunk carry
    FL = F[:, -1, :]                                         # [B,H]
    a_end = FL[:, None, :] - F + i_pre                       # [B,L,H]
    m_new = jnp.maximum(m0 + FL, jnp.max(a_end, axis=1))
    scale_old = jnp.exp(m0 + FL - m_new)                     # [B,H]
    wk_end = jnp.exp(a_end - m_new[:, None, :])              # [B,L,H]
    C_new = C0 * scale_old[..., None, None] + jnp.einsum("blh,blhd,blhe->bhde", wk_end, kf, vf)
    n_new = n0 * scale_old[..., None] + jnp.einsum("blh,blhd->bhd", wk_end, kf)
    return h, (C_new, n_new, m_new)


def mlstm_apply(cfg: ModelConfig, p: Params, x: jax.Array, *, mode: str,
                ctx: ShardCtx = NULL_CTX, cache: Optional[Params] = None,
                ) -> Tuple[jax.Array, Optional[Params]]:
    d_in, H, dk = _mlstm_dims(cfg)
    dt = x.dtype
    B, S, _ = x.shape
    xz = x @ p["up_proj"].astype(dt)
    xm, z = xz[..., :d_in], xz[..., d_in:]
    xm = ctx.constrain(xm, ("batch", "seq", "act_heads"))

    q = (xm @ p["wq"].astype(dt)).reshape(B, S, H, dk)
    k = (xm @ p["wk"].astype(dt)).reshape(B, S, H, dk)
    v = (xm @ p["wv"].astype(dt)).reshape(B, S, H, dk)
    i_pre = (xm @ p["w_i"].astype(dt)).astype(jnp.float32) + p["b_i"].astype(jnp.float32)
    log_f = jax.nn.log_sigmoid(
        (xm @ p["w_f"].astype(dt)).astype(jnp.float32) + p["b_f"].astype(jnp.float32))

    if mode == "decode":
        carry = (cache["C"].astype(jnp.float32), cache["n"].astype(jnp.float32),
                 cache["m"].astype(jnp.float32))
        h, (C1, n1, m1) = _mlstm_chunk(q, k, v, i_pre, log_f, carry)
        new_cache = {"C": C1, "n": n1, "m": m1}
    else:
        L = min(cfg.xlstm.chunk, S)
        while S % L:          # largest divisor <= chunk (exact state carry)
            L -= 1
        nchunk = S // L

        def rs(t):
            return jnp.moveaxis(t.reshape(B, nchunk, L, *t.shape[2:]), 1, 0)

        def step(carry, inp):
            h, carry = _mlstm_chunk(*inp, carry)
            return carry, h

        carry0 = (jnp.zeros((B, H, dk, dk), jnp.float32),
                  jnp.zeros((B, H, dk), jnp.float32),
                  jnp.full((B, H), MIN_LOG, jnp.float32))
        carry, hs = jax.lax.scan(step, carry0, (rs(q), rs(k), rs(v), rs(i_pre), rs(log_f)))
        h = jnp.moveaxis(hs, 0, 1).reshape(B, S, H, dk)
        new_cache = ({"C": carry[0], "n": carry[1], "m": carry[2]}
                     if mode == "prefill" else None)

    h = h.reshape(B, S, d_in).astype(dt)
    h = _headwise_rms(h, p["mh_norm"], H, cfg.norm_eps)
    out = (h * jax.nn.silu(z)) @ p["down_proj"].astype(dt)
    return out, new_cache


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------

def slstm_apply(cfg: ModelConfig, p: Params, x: jax.Array, *, mode: str,
                ctx: ShardCtx = NULL_CTX, cache: Optional[Params] = None,
                ) -> Tuple[jax.Array, Optional[Params]]:
    d, H = cfg.d_model, cfg.num_heads
    dh = d // H
    dt = x.dtype
    B, S, _ = x.shape
    pre = (x @ p["w_x"].astype(dt)).astype(jnp.float32) + p["b_x"].astype(jnp.float32)
    pre = pre.reshape(B, S, 4, H, dh)                        # z, i, f, o
    r = p["r"].astype(jnp.float32)                           # [4,H,dh,dh]

    if cache is not None:
        st0 = (cache["c"].astype(jnp.float32).reshape(B, H, dh),
               cache["n"].astype(jnp.float32).reshape(B, H, dh),
               cache["h"].astype(jnp.float32).reshape(B, H, dh),
               cache["m"].astype(jnp.float32))
    else:
        st0 = (jnp.zeros((B, H, dh), jnp.float32), jnp.zeros((B, H, dh), jnp.float32),
               jnp.zeros((B, H, dh), jnp.float32), jnp.full((B, H), MIN_LOG, jnp.float32))

    def step(st, pre_t):                                     # pre_t [B,4,H,dh]
        c, n, h, m = st
        rec = jnp.einsum("bhd,ghde->gbhe", h, r)             # [4,B,H,dh]
        zt = jnp.tanh(pre_t[:, 0] + rec[0])
        it = pre_t[:, 1] + rec[1]                            # log-space input gate
        ft = jax.nn.log_sigmoid(pre_t[:, 2] + rec[2])        # log forget
        ot = jax.nn.sigmoid(pre_t[:, 3] + rec[3])
        # stabilizer per head: use max over head dims of gate pre-activations
        it_h = jnp.max(it, axis=-1)                          # [B,H]
        ft_h = jnp.min(ft, axis=-1)
        m_new = jnp.maximum(ft_h + m, it_h)
        ip = jnp.exp(it - m_new[..., None])
        fp = jnp.exp(ft + (m - m_new)[..., None])
        c_new = fp * c + ip * zt
        n_new = fp * n + ip
        h_new = ot * c_new / jnp.maximum(n_new, 1e-6)
        return (c_new, n_new, h_new, m_new), h_new

    st, hs = jax.lax.scan(step, st0, jnp.moveaxis(pre, 1, 0))
    h = jnp.moveaxis(hs, 0, 1).reshape(B, S, d)

    new_cache = None
    if mode in ("prefill", "decode"):
        c, n, hh, m = st
        new_cache = {"c": c.reshape(B, d), "n": n.reshape(B, d),
                     "h": hh.reshape(B, d), "m": m}

    out = _headwise_rms(h.astype(dt), p["gn"], H, cfg.norm_eps)
    return out, new_cache
