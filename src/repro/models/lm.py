"""Decoder LM assembly: superblock ``lax.scan`` over heterogeneous stacks.

Covers families dense / moe / hybrid / ssm / vlm (whisper enc-dec lives in
``whisper.py``; ``api.py`` dispatches). The layer stack is
``num_periods = num_layers / len(block_pattern)`` scan iterations; each
iteration applies one period of (mixer, mlp) blocks, so Jamba's 1:7
attn:mamba interleave and xLSTM's m/s pattern compile as a single scan.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ShardCtx, NULL_CTX
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import mamba as mb
from repro.models import moe as moe_mod
from repro.models import xlstm as xl
from repro.models.params import ParamDef, init_params, stack_defs

Params = Dict[str, Any]

MIXER_HAS_ROPE = {"attn"}


# ---------------------------------------------------------------------------
# Defs
# ---------------------------------------------------------------------------

def _mixer_defs(cfg: ModelConfig, mixer: str) -> Params:
    if mixer == "attn":
        return attn.attn_defs(cfg)
    if mixer == "mamba":
        return mb.mamba_defs(cfg)
    if mixer == "mlstm":
        return xl.mlstm_defs(cfg)
    if mixer == "slstm":
        return xl.slstm_defs(cfg)
    raise ValueError(mixer)


def _mlp_defs(cfg: ModelConfig, mlp: str) -> Optional[Params]:
    if mlp == "mlp":
        return L.mlp_defs(cfg)
    if mlp == "moe":
        return moe_mod.moe_defs(cfg)
    if mlp == "glu":
        d_ff = int(cfg.xlstm.slstm_ffn_factor * cfg.d_model) if cfg.xlstm else cfg.d_ff
        return L.mlp_defs(cfg, d_ff)
    if mlp == "none":
        return None
    raise ValueError(mlp)


def block_defs(cfg: ModelConfig, mixer: str, mlp: str) -> Params:
    out: Params = {"mixer_norm": L.norm_defs(cfg), "mixer": _mixer_defs(cfg, mixer)}
    m = _mlp_defs(cfg, mlp)
    if m is not None:
        out["mlp_norm"] = L.norm_defs(cfg)
        out["mlp"] = m
    return out


# Parameter and cache key of the leading dense layers, beside ``blocks``.
LEAD = "lead"


def lm_defs(cfg: ModelConfig) -> Params:
    blocks = {}
    for i, (mixer, mlp) in enumerate(cfg.block_pattern):
        blocks[f"pos{i}"] = stack_defs(block_defs(cfg, mixer, mlp),
                                       cfg.num_periods, "layers")
    out = {"embed": L.embed_defs(cfg), "blocks": blocks,
           "final_norm": L.norm_defs(cfg)}
    if cfg.leading_dense_layers:
        out[LEAD] = stack_defs(block_defs(cfg, "attn", "mlp"),
                               cfg.leading_dense_layers, "layers")
    return out


def init(cfg: ModelConfig, key: jax.Array) -> Params:
    return init_params(lm_defs(cfg), key, cfg.param_dtype)


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def _pos_cache_shapes(cfg: ModelConfig, mixer: str, batch: int, max_len: int) -> Optional[Dict]:
    if mixer == "attn":
        return attn.init_cache(cfg, batch, max_len)
    if mixer == "mamba":
        return mb.mamba_cache_shapes(cfg, batch)
    if mixer == "mlstm":
        return xl.mlstm_cache_shapes(cfg, batch)
    if mixer == "slstm":
        return xl.slstm_cache_shapes(cfg, batch)
    raise ValueError(mixer)


def _cache_dtype(cfg: ModelConfig, mixer: str, name: str) -> jnp.dtype:
    if mixer == "attn":
        return jnp.dtype(cfg.dtype)
    if mixer == "mamba" and name == "conv":
        return jnp.dtype(cfg.dtype)
    return jnp.dtype(jnp.float32)


def cache_spec(cfg: ModelConfig, batch: int, max_len: int):
    """(shapes, dtypes, logical_axes) trees for the stacked cache."""
    shapes: Params = {}
    dtypes: Params = {}
    axes: Params = {}
    stacks = [(f"pos{i}", mixer, cfg.num_periods)
              for i, (mixer, _) in enumerate(cfg.block_pattern)]
    if cfg.leading_dense_layers:
        stacks.append((LEAD, "attn", cfg.leading_dense_layers))
    for key, mixer, n in stacks:
        sh = _pos_cache_shapes(cfg, mixer, batch, max_len)
        shapes[key] = {k: (n,) + tuple(v) for k, v in sh.items()}
        dtypes[key] = {k: _cache_dtype(cfg, mixer, k) for k in sh}
        if mixer == "attn":
            ax = attn.cache_axes(cfg)
        else:
            ax = {k: ("layers", "cache_batch") + (None,) * (len(v) - 1)
                  for k, v in sh.items()}
        axes[key] = ax
    return shapes, dtypes, axes


def cache_sds(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    shapes, dtypes, _ = cache_spec(cfg, batch, max_len)
    return jax.tree.map(lambda s, d: jax.ShapeDtypeStruct(s, d), shapes, dtypes,
                        is_leaf=lambda x: isinstance(x, tuple))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _rope_for(cfg: ModelConfig, positions: jax.Array,
              mrope_positions: Optional[jax.Array]):
    if cfg.attention_type == "mla":
        rot = cfg.mla.qk_rope_head_dim
    else:
        rot = int(cfg.partial_rotary * cfg.resolved_head_dim)
        rot -= rot % 2
    if rot == 0:
        return None  # e.g. Jamba: attention layers carry no positional encoding
    if cfg.vision is not None and mrope_positions is not None:
        return L.mrope_tables(mrope_positions, cfg.vision.mrope_sections, rot, cfg.rope_theta)
    if cfg.yarn is not None:
        return L.yarn_tables(positions, rot, cfg.rope_theta, cfg.yarn)
    return L.rope_tables(positions, rot, cfg.rope_theta)


def _apply_block(cfg: ModelConfig, p: Params, x: jax.Array, mixer: str, mlp: str,
                 *, rope, mode: str, ctx: ShardCtx, cache, pos, experts=None):
    h = L.apply_norm(cfg, p["mixer_norm"], x)
    if mixer == "attn":
        fn = attn.mla_apply if cfg.attention_type == "mla" else attn.gqa_apply
        y, new_cache = fn(cfg, p["mixer"], h, rope=rope, mode=mode, ctx=ctx,
                          cache=cache, pos=pos)
    elif mixer == "mamba":
        y, new_cache = mb.mamba_apply(cfg, p["mixer"], h, mode=mode, ctx=ctx, cache=cache)
    elif mixer == "mlstm":
        y, new_cache = xl.mlstm_apply(cfg, p["mixer"], h, mode=mode, ctx=ctx, cache=cache)
    elif mixer == "slstm":
        y, new_cache = xl.slstm_apply(cfg, p["mixer"], h, mode=mode, ctx=ctx, cache=cache)
    else:
        raise ValueError(mixer)
    x = x + y
    aux = {}
    if mlp != "none":
        h = L.apply_norm(cfg, p["mlp_norm"], x)
        if mlp == "moe":
            y, aux = moe_mod.moe_apply(cfg, p["mlp"], h, ctx, mode=mode,
                                       experts=experts)
        else:
            y = L.apply_mlp(cfg, p["mlp"], h, ctx)
        x = x + y
    x = ctx.constrain(x, ("batch", "seq", None))
    return x, new_cache, aux


def forward(cfg: ModelConfig, params: Params, tokens: jax.Array, *,
            mode: str = "train", ctx: ShardCtx = NULL_CTX,
            cache: Optional[Params] = None, pos: Optional[jax.Array] = None,
            vision_embeds: Optional[jax.Array] = None,
            mrope_positions: Optional[jax.Array] = None,
            ) -> Tuple[jax.Array, Optional[Params], Dict[str, jax.Array]]:
    """tokens [B, S] -> (hidden [B,S,D], new_cache, aux). ``pos`` is the cache
    fill index for decode (scalar int32)."""
    B, S = tokens.shape
    x = L.embed_tokens(cfg, params["embed"], tokens, ctx)
    if cfg.vision is not None and vision_embeds is not None:
        n_img = vision_embeds.shape[1]
        x = jnp.concatenate([vision_embeds.astype(x.dtype), x[:, n_img:]], axis=1)

    if mode == "decode":
        positions = jnp.full((B, S), pos, jnp.int32)
    else:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if mrope_positions is None and cfg.vision is not None:
        mrope_positions = jnp.broadcast_to(positions[:, None, :], (B, 3, S))
    rope = _rope_for(cfg, positions, mrope_positions)

    has_cache = cache is not None
    want_cache = mode in ("prefill", "decode")

    lead_cache = None
    if cfg.leading_dense_layers:
        def lead_body(x, per_layer):
            p, c = per_layer
            x, nc, _ = _apply_block(cfg, p, x, "attn", "mlp", rope=rope, mode=mode,
                                    ctx=ctx, cache=c, pos=pos)
            return x, (nc if want_cache else None)

        if has_cache:
            cache = dict(cache)
            xs_lead = cache.pop(LEAD)
        else:
            xs_lead = jax.tree.map(lambda _: None, params[LEAD])
        x, lead_cache = jax.lax.scan(lead_body, x, (params[LEAD], xs_lead))

    # An expert-parallel share reads every layer's held experts as one stack
    # (``moe._dropless``), so its layer index rides along the scan.
    stacks = params["blocks"] if moe_mod.serves_share(cfg, mode) else None

    def period_body(x, per_layer):
        p_by_pos, c_by_pos, layer = per_layer
        new_caches = {}
        aux_sum = None
        for i, (mixer, mlp) in enumerate(cfg.block_pattern):
            c_i = c_by_pos[f"pos{i}"] if has_cache else None
            experts = (stacks[f"pos{i}"]["mlp"], layer) \
                if stacks is not None and mlp == "moe" else None
            x, nc, aux = _apply_block(cfg, p_by_pos[f"pos{i}"], x, mixer, mlp,
                                      rope=rope, mode=mode, ctx=ctx, cache=c_i, pos=pos,
                                      experts=experts)
            if want_cache:
                new_caches[f"pos{i}"] = nc
            if aux:
                aux_sum = aux if aux_sum is None else jax.tree.map(jnp.add, aux_sum, aux)
        return x, (new_caches, aux_sum if aux_sum is not None else {})

    xs_cache = cache if has_cache else jax.tree.map(lambda _: None, params["blocks"])
    body = period_body
    if mode == "train" and cfg.remat != "none":
        policy = (jax.checkpoint_policies.nothing_saveable if cfg.remat == "full"
                  else jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
        body = jax.checkpoint(period_body, policy=policy)
    layers = jnp.arange(cfg.num_periods) if stacks is not None else None
    x, (new_cache, auxs) = jax.lax.scan(body, x, (params["blocks"], xs_cache, layers))

    if want_cache and lead_cache is not None:
        new_cache = {**new_cache, LEAD: lead_cache}
    x = L.apply_norm(cfg, params["final_norm"], x)
    aux_out = {k: jnp.sum(v) for k, v in auxs.items()} if auxs else {}
    return x, (new_cache if want_cache else None), aux_out


# ---------------------------------------------------------------------------
# Losses / steps
# ---------------------------------------------------------------------------

def chunked_xent(cfg: ModelConfig, params: Params, h: jax.Array,
                 labels: jax.Array, ctx: ShardCtx = NULL_CTX) -> jax.Array:
    """Cross-entropy without materializing [B,S,V] logits for the full seq:
    scan over sequence chunks, remat'd so backward recomputes per-chunk."""
    W = L.unembed_matrix(cfg, params["embed"])
    B, S, D = h.shape
    Lc = cfg.loss_chunk if S % max(cfg.loss_chunk, 1) == 0 and cfg.loss_chunk > 0 else S
    n = S // Lc

    def chunk_nll(hc, lc):
        # All dots in the model dtype (bf16): the f32 casts sit AFTER the
        # matmuls so the backward cotangent entering the residual stream is
        # bf16 — an f32 gold-logit dot here made the ENTIRE backward pass
        # run in f32 (2x collective + memory traffic; §Perf global fix).
        logits = (hc @ W.astype(hc.dtype))
        logits = ctx.constrain(logits, ("batch", "seq", "vocab"))
        logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        # label logit via embedding-row gather (avoids take_along_axis over the
        # vocab-sharded [B,L,V] tensor — GSPMD handles the row gather cheaply)
        w_label = jnp.take(W.T, lc, axis=0).astype(hc.dtype)      # [B,L,D]
        gold = jnp.sum(hc * w_label, axis=-1).astype(jnp.float32)
        zreg = 1e-4 * jnp.square(logz)
        return jnp.sum(logz - gold + zreg)

    chunk_nll = jax.checkpoint(chunk_nll, policy=jax.checkpoint_policies.nothing_saveable)

    # Unrolled python loop (not lax.scan): chunk count is small and keeping it
    # out of a `while` op makes compiled cost_analysis FLOPs exact.
    total = jnp.zeros((), jnp.float32)
    for i in range(n):
        total = total + chunk_nll(h[:, i * Lc:(i + 1) * Lc, :],
                                  labels[:, i * Lc:(i + 1) * Lc])
    return total / (B * S)


def loss_fn(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
            ctx: ShardCtx = NULL_CTX) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    h, _, aux = forward(cfg, params, batch["tokens"], mode="train", ctx=ctx,
                        vision_embeds=batch.get("vision_embeds"),
                        mrope_positions=batch.get("mrope_positions"))
    loss = chunked_xent(cfg, params, h, batch["labels"], ctx)
    metrics = {"xent": loss}
    if "moe_aux_loss" in aux:
        loss = loss + aux["moe_aux_loss"]
        metrics.update({k: aux[k] for k in ("moe_aux_loss", "moe_lb", "moe_drop_frac")})
    metrics["loss"] = loss
    return loss, metrics


def logits_at_last(cfg: ModelConfig, params: Params, h: jax.Array,
                   ctx: ShardCtx = NULL_CTX) -> jax.Array:
    W = L.unembed_matrix(cfg, params["embed"])
    out = (h[:, -1:, :] @ W.astype(h.dtype)).astype(jnp.float32)
    return ctx.constrain(out, ("batch", "seq", "vocab"))


def prefill(cfg: ModelConfig, params: Params, tokens: jax.Array, *,
            ctx: ShardCtx = NULL_CTX, vision_embeds=None, mrope_positions=None):
    h, cache, _ = forward(cfg, params, tokens, mode="prefill", ctx=ctx,
                          vision_embeds=vision_embeds, mrope_positions=mrope_positions)
    return logits_at_last(cfg, params, h, ctx), cache


def decode_step(cfg: ModelConfig, params: Params, cache: Params,
                token: jax.Array, pos: jax.Array, *, ctx: ShardCtx = NULL_CTX,
                counters: bool = False):
    """token [B,1]; pos scalar int32 (index where this token is written).
    With ``counters``, a model with expert-parallel layers also returns their
    ``moe.MOE_COUNTERS`` for the step, summed over its layers, as int32 [3]."""
    h, new_cache, aux = forward(cfg, params, token, mode="decode", ctx=ctx,
                                cache=cache, pos=pos)
    logits = logits_at_last(cfg, params, h, ctx)
    if counters and moe_mod.MOE_COUNTERS[0] in aux:
        return logits, new_cache, jnp.stack([aux[k] for k in moe_mod.MOE_COUNTERS])
    return logits, new_cache
