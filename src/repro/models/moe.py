"""Mixture-of-Experts with sort-based capacity dispatch.

Design notes (roofline-driven): the classic GShard one-hot dispatch einsum
[T,D]x[T,E,C] costs k*cf*T^2*D FLOPs — quadratic in tokens, catastrophic at
T=1M (train_4k). We instead sort token-expert assignments by expert id and
gather into a fixed [E, C, D] buffer: dispatch is pure data movement (gather/
scatter, O(T*k*D) bytes, zero matmul FLOPs) and expert compute is a batched
einsum costing exactly k*cf x the active FLOPs — so compiled HLO FLOPs track
6*N_active*D. Expert weights shard over the 'model' axis (EP); token->slot
assembly happens per-DP-shard (the LM wraps this under one GSPMD program, and
for very large T the caller lowers it inside shard_map over the DP axes).

For tiny token counts (decode steps) the sort overhead is irrelevant and the
same path is used.

An expert-parallel layer (``MoEConfig.router_experts`` set) is one rank's
share: the router scores all ``router_experts`` experts, this rank holds
experts [0, num_experts) and adds only their part of the result. It serves
(prefill, decode) without drops: the assignments that land on held experts
are sorted by expert and run through one grouped matmul per projection
(megablox's ``gmm``) with no capacity, and each token sums its held
assignments' rows, weighted by their router probabilities. The grouped
matmuls read the held experts of every layer as one stack, with this
layer's group sizes at its offset and the others' zero: a layer's slice of
a stack scanned over layers would be copied out before each call. Each such
layer counts, per call, the assignments it served, its busiest expert's
tokens and the assignments it dropped (``MOE_COUNTERS``).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm as megablox_gmm

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ShardCtx, NULL_CTX
from repro.models.params import ParamDef, dense

Params = Dict[str, Any]

# Tokens one grouped matmul of a dropless layer takes at most: a longer
# prefill runs in blocks of this many, which bounds its [tokens * top_k,
# d_model] buffers.
DROPLESS_BLOCK = 8192

# Grouped matmul tiles: rows near a group's expected size within these
# bounds (each tile reads its group's whole weight, so a tile much taller
# than the group computes padding, and a much shorter one rereads the
# weight), and blocks within the VMEM a kernel may take by default (16 MiB
# on v5e), double-buffered. On a v5e the decode step's 16 experts of 12
# rows ran 11.5 ms a step at 128 rows and 12.5 ms at 256 (PERF.md).
GMM_ROWS = (128, 512)
GMM_VMEM = 14 * 2**20

# The routed experts' weights, [E, ...] in a layer's parameters.
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")

# Per-call counts of an expert-parallel layer served without drops, summed
# over its layers: top-k assignments that landed on held experts, the busiest
# held expert's tokens, and held assignments left out of the grouped matmul.
MOE_COUNTERS = ("moe_routed", "moe_busiest", "moe_dropped")


def moe_defs(cfg: ModelConfig) -> Params:
    m = cfg.moe
    d = cfg.d_model
    f = m.d_expert or cfg.d_ff
    e = m.num_experts
    out: Params = {
        "router": dense(d, m.router_experts or e, ("embed", None), scale=d ** -0.5),
        "w_gate": ParamDef((e, d, f), ("expert", "embed", "ff"), "normal", d ** -0.5),
        "w_up": ParamDef((e, d, f), ("expert", "embed", "ff"), "normal", d ** -0.5),
        "w_down": ParamDef((e, f, d), ("expert", "ff", "embed"), "normal", f ** -0.5),
    }
    if m.num_shared:
        fs = f * m.num_shared
        out["shared"] = {
            "wi_gate": dense(d, fs, ("embed", "ff")),
            "wi_up": dense(d, fs, ("embed", "ff")),
            "wo": dense(fs, d, ("ff", "embed")),
        }
    return out


def _top_k(m, probs: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Greedy top-k of the router's probabilities: (weights, expert ids)."""
    top_p, top_i = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    return top_p, top_i


def _capacity(cfg: ModelConfig, T: int) -> int:
    m = cfg.moe
    c = int(m.capacity_factor * m.top_k * T / m.num_experts)
    return max(8, -(-c // 8) * 8)  # >=8, round up to multiple of 8


def _dispatch_group(cfg: ModelConfig, p: Params, xt: jax.Array, C: int):
    """Sort-based dispatch/combine for ONE token group [T, D] (shard-local:
    the caller vmaps this over DP groups so every sort/gather/scatter stays
    on-device — §Perf fix: the global-token version made GSPMD materialize
    partial [E*C, D] buffers and all-reduce them, 100x collective blowup)."""
    m = cfg.moe
    T, D = xt.shape
    k, E = m.top_k, m.num_experts
    dt = xt.dtype

    # ---- routing (fp32) ----
    logits = (xt @ p["router"].astype(dt)).astype(jnp.float32)      # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = _top_k(m, probs)                                 # [T, k]
    top_w = top_p.astype(dt)

    # ---- sort assignments by expert ----
    flat_e = top_i.reshape(-1)                                      # [T*k]
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    flat_w = top_w.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]

    counts = jnp.zeros((E,), jnp.int32).at[se].add(1)               # tokens/expert
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(T * k, dtype=jnp.int32) - starts[se]
    keep = pos_in_e < C
    slot = se * C + jnp.clip(pos_in_e, 0, C - 1)                    # [T*k]

    # ---- dispatch: gather tokens into [E, C, D] ----
    x_sorted = jnp.where(keep[:, None], xt[st], 0)
    buf = jnp.zeros((E * C, D), dt).at[slot].add(x_sorted)          # dropped -> +0
    xe = buf.reshape(E, C, D)

    # ---- expert FFN (batched einsum; k*cf x active FLOPs) ----
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"].astype(dt)))
    h = h * jnp.einsum("ecd,edf->ecf", xe, p["w_up"].astype(dt))
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dt)).reshape(E * C, D)

    # ---- combine: gather back, weight, scatter-add over tokens ----
    out_sorted = ye[slot] * jnp.where(keep, sw, 0)[:, None]
    out = jnp.zeros((T, D), dt).at[st].add(out_sorted)
    return out, (counts, probs, logits, keep)


def _gmm_tiling(m: int, k: int, n: int, itemsize: int,
                per_group: int) -> Tuple[int, int, int]:
    """Tiles of rows, contraction and columns for the grouped matmul over
    ``m`` rows of groups of about ``per_group`` rows: rows dividing ``m``,
    then the largest blocks that fit ``GMM_VMEM`` double-buffered, halving
    the columns (while they stay whole lanes) before the contraction."""
    lo, hi = GMM_ROWS
    tm = math.gcd(m, min(max(lo, 1 << max(per_group - 1, 0).bit_length()), hi))
    if tm % 8:
        tm = m
    tk, tn = k, n

    def size(tk, tn):
        return 2 * itemsize * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn

    while size(tk, tn) > GMM_VMEM and tn % 256 == 0:
        tn //= 2
    while size(tk, tn) > GMM_VMEM and tk > 128:
        tk = max(128, tk // 2 // 128 * 128)
    return tm, tk, tn


def _grouped(x: jax.Array, w: jax.Array, group_sizes: jax.Array,
             per_group: int) -> jax.Array:
    """x [m, K], rows sorted by group -> [m, N]: group g's rows times w[g],
    groups of about ``per_group`` rows.
    Rows past the groups are not computed (their values are undefined); a
    group of size 0 is not visited, nor is its weight read (megablox's
    grouped matmul; interpreted where the program is lowered for another
    platform than the TPU)."""
    m, k = x.shape
    tiling = _gmm_tiling(m, k, w.shape[2], x.dtype.itemsize, per_group)

    def gmm(interpret):
        return lambda x, w, g: megablox_gmm(x, w, g, x.dtype, tiling, None, None,
                                            False, interpret)

    return jax.lax.platform_dependent(x, w, group_sizes, tpu=gmm(False),
                                      default=gmm(True))


def serves_share(cfg: ModelConfig, mode: str) -> bool:
    """Whether ``moe_apply`` serves an expert-parallel share without drops
    (and takes the stack of every layer's held experts)."""
    return bool(cfg.moe and cfg.moe.router_experts) and mode in ("prefill", "decode")


def _dropless(cfg: ModelConfig, p: Params, xt: jax.Array, stack: Params,
              layer: jax.Array):
    """An expert-parallel share served without drops, for tokens [T, D]:
    route over all ``router_experts``, sort the assignments that land on the
    held experts by expert (the others last), one grouped matmul per
    projection over the held rows, and per token the weighted sum of its
    held assignments' rows, gathered back from the sorted order. ``stack``
    holds the held experts of every layer ([layers, E, ...]); this layer is
    ``layer`` of them."""
    m = cfg.moe
    T, D = xt.shape
    k, E = m.top_k, m.num_experts
    dt = xt.dtype

    logits = jnp.dot(xt, p["router"].astype(dt),
                     preferred_element_type=jnp.float32)             # [T, E_r]
    top_w, top_i = _top_k(m, jax.nn.softmax(logits, axis=-1))       # [T, k]
    held = top_i < E
    expert = jnp.where(held, top_i, E).reshape(-1)                  # E: not held
    order = jnp.argsort(expert, stable=True)                        # rows by expert
    sizes = jnp.zeros((E + 1,), jnp.int32).at[expert].add(1)[:E]    # rows/expert
    row = jnp.zeros_like(order).at[order].set(
        jnp.arange(T * k, dtype=order.dtype))                       # row of each

    groups = stack["w_gate"].shape[0] * E
    group_sizes = jax.lax.dynamic_update_slice(
        jnp.zeros((groups,), jnp.int32), sizes, (layer * E,))
    w = {n: stack[n].reshape((groups,) + stack[n].shape[2:]).astype(dt)
         for n in EXPERT_WEIGHTS}
    per_group = T * k // m.router_experts           # uniform routing
    xs = xt[order // k]
    h = jax.nn.silu(_grouped(xs, w["w_gate"], group_sizes, per_group))
    h = h * _grouped(xs, w["w_up"], group_sizes, per_group)
    ys = _grouped(h, w["w_down"], group_sizes, per_group)
    # Rows past the held ones belong to no group and hold no value: the
    # assignments that read them are masked out.
    y = ys[row].reshape(T, k, D).astype(jnp.float32)
    out = jnp.sum(jnp.where(held[..., None], y * top_w[..., None], 0.0), 1).astype(dt)

    routed = jnp.sum(held, dtype=jnp.int32)
    counts = dict(zip(MOE_COUNTERS, (routed, jnp.max(sizes),
                                     routed - jnp.sum(sizes))))
    return out, counts


def _shared(p: Params, xt: jax.Array) -> jax.Array:
    dt = xt.dtype
    sp = p["shared"]
    hs = jax.nn.silu(xt @ sp["wi_gate"].astype(dt)) * (xt @ sp["wi_up"].astype(dt))
    return hs @ sp["wo"].astype(dt)


def moe_apply(cfg: ModelConfig, p: Params, x: jax.Array,
              ctx: ShardCtx = NULL_CTX, mode: str = "train",
              experts: Optional[Tuple[Params, jax.Array]] = None,
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: [B, S, D] -> (out [B, S, D], aux). An expert-parallel layer serves
    (``serves_share``) without drops, with ``MOE_COUNTERS`` as its aux, its
    experts read from ``experts`` = (the stack of every layer's
    ``EXPERT_WEIGHTS``, this layer's index), or where that is not given
    from ``p`` alone; otherwise the capacity dispatch below, with the
    load-balance loss."""
    with jax.named_scope("truffle.moe"):
        if serves_share(cfg, mode):
            stack, layer = experts or ({n: p[n][None] for n in EXPERT_WEIGHTS}, 0)
            B, S, D = x.shape
            T = B * S
            xt = x.reshape(T, D)
            if T > DROPLESS_BLOCK and T % DROPLESS_BLOCK == 0:
                out, counts = jax.lax.map(
                    lambda xb: _dropless(cfg, p, xb, stack, layer),
                    xt.reshape(-1, DROPLESS_BLOCK, D))
                out = out.reshape(T, D)
                counts = {k: jnp.sum(v) for k, v in counts.items()}
            else:
                out, counts = _dropless(cfg, p, xt, stack, layer)
            if cfg.moe.num_shared:
                out = out + _shared(p, xt)
            return out.reshape(B, S, D), counts
        return _capacity_apply(cfg, p, x, ctx)


def _capacity_apply(cfg: ModelConfig, p: Params, x: jax.Array,
                    ctx: ShardCtx) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: [B, S, D] -> (out [B, S, D], aux metrics incl. load-balance loss).

    Tokens are regrouped [B,S,D] -> [dp, T/dp, D] along the DP shard
    boundary and the dispatch is vmapped per group: sort/gather/scatter are
    shard-local, expert weights stay EP-sharded over 'model' through the
    batched einsums. Per-group capacity keeps drop semantics local."""
    m = cfg.moe
    if m.router_experts not in (0, m.num_experts):
        raise ValueError(f"the capacity dispatch holds every expert it routes "
                         f"over; this layer holds {m.num_experts} of "
                         f"{m.router_experts}")
    B, S, D = x.shape
    T = B * S
    k, E = m.top_k, m.num_experts
    dt = x.dtype

    dp = ctx.axis_size("batch")
    if B % dp != 0:
        dp = 1
    Tl = T // dp
    xg = x.reshape(dp, Tl, D)
    xg = ctx.constrain(xg, ("dp_groups", None, None))
    C = _capacity(cfg, Tl)

    out_g, (counts, probs, logits, keep) = jax.vmap(
        lambda xt: _dispatch_group(cfg, p, xt, C))(xg)
    out_g = ctx.constrain(out_g, ("dp_groups", None, None))
    out = out_g.reshape(T, D)
    xt = x.reshape(T, D)

    if m.num_shared:
        out = out + _shared(p, xt)

    # ---- aux losses (Switch-style load balance + router z-loss) ----
    frac = jnp.sum(counts, 0).astype(jnp.float32) / (T * k)  # dispatch fraction
    mean_p = jnp.mean(probs, axis=(0, 1))
    lb_loss = E * jnp.sum(frac * mean_p)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    aux = {
        "moe_aux_loss": m.aux_loss_coef * lb_loss + m.router_z_coef * z_loss,
        "moe_lb": lb_loss,
        "moe_drop_frac": 1.0 - jnp.mean(keep.astype(jnp.float32)),
    }
    return out.reshape(B, S, D), aux
