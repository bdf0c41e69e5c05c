"""Analytic MODEL_FLOPS per (arch x shape): the "useful work" reference the
roofline compares compiled HLO FLOPs against (6ND-style accounting + explicit
attention/SSM terms; no remat, no dispatch overhead)."""
from __future__ import annotations

from repro.configs.base import ModelConfig, ShapeConfig


def _matmul_params(cfg: ModelConfig) -> int:
    """Active params that participate in matmuls (embedding gather excluded,
    unembedding projection included)."""
    n = cfg.param_count(active_only=True)
    n -= cfg.vocab_size * cfg.d_model          # embedding table (gather)
    if cfg.tie_embeddings:
        n += cfg.vocab_size * cfg.d_model      # ...but the tied head is a matmul
    return n


def _attn_layers(cfg: ModelConfig) -> int:
    per = sum(1 for m, _ in cfg.block_pattern if m == "attn")
    return per * cfg.num_periods + cfg.leading_dense_layers


def _mixer_layers(cfg: ModelConfig, kind: str) -> int:
    per = sum(1 for m, _ in cfg.block_pattern if m == kind)
    return per * cfg.num_periods


def _attn_fwd_flops(cfg: ModelConfig, B: int, Sq: int, Skv: int,
                    causal: bool) -> float:
    """QK^T + AV matmul flops for ONE layer, forward."""
    H = cfg.num_heads
    if cfg.attention_type == "mla":
        qk = cfg.mla.qk_nope_head_dim + cfg.mla.qk_rope_head_dim
        vd = cfg.mla.v_head_dim
    else:
        qk = vd = cfg.resolved_head_dim
    f = 2.0 * B * Sq * Skv * H * (qk + vd)
    return f * (0.5 if causal and Sq == Skv else 1.0)


def _ssm_fwd_flops(cfg: ModelConfig, B: int, S: int) -> float:
    """Mamba selective-scan elementwise work for ONE layer, forward."""
    mc = cfg.mamba
    d_in = mc.expand * cfg.d_model
    return 9.0 * B * S * d_in * mc.d_state


def _mlstm_fwd_flops(cfg: ModelConfig, B: int, S: int) -> float:
    """Chunkwise mLSTM: intra-chunk quadratic + inter-chunk state einsums."""
    x = cfg.xlstm
    d_in = int(x.mlstm_proj_factor * cfg.d_model)
    H = cfg.num_heads
    dk = d_in // H
    c = min(x.chunk, S)
    intra = 2.0 * B * S * c * H * (2 * dk + dk) * 0.5      # qk + av, causal
    inter = 4.0 * B * S * H * dk * dk + 4.0 * B * S * H * dk  # q@C + kv^T accum
    return intra + inter


def _slstm_fwd_flops(cfg: ModelConfig, B: int, S: int) -> float:
    H = cfg.num_heads
    dh = cfg.d_model // H
    return 8.0 * B * S * H * dh * dh  # 4 recurrent gate einsums


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        tokens, mult = B * S, 6.0
    elif shape.kind == "prefill":
        tokens, mult = B * S, 2.0
    else:  # decode: per step, one token each
        tokens, mult = B, 2.0

    mm_params = _matmul_params(cfg)
    if cfg.encoder is not None and shape.kind == "decode":
        # decode never runs the encoder; cross K/V projections are cached
        d, hd, nkv = cfg.d_model, cfg.resolved_head_dim, cfg.num_kv_heads
        enc_attn = d * cfg.num_heads * hd * 2 + 2 * d * nkv * hd
        enc_mlp = (3 if cfg.act == "silu" else 2) * d * cfg.d_ff
        mm_params -= cfg.encoder.num_layers * (enc_attn + enc_mlp)
        mm_params -= cfg.num_layers * 2 * d * nkv * hd
    total = mult * mm_params * tokens
    fwd_share = mult / 2.0  # fwd(+bwd): train 3x fwd, inference 1x

    if shape.kind == "decode":
        attn = _attn_fwd_flops(cfg, B, 1, S, causal=False)
        ssm = _ssm_fwd_flops(cfg, B, 1) if cfg.mamba else 0.0
        mls = _mlstm_fwd_flops(cfg, B, 1) if cfg.xlstm else 0.0
        sls = _slstm_fwd_flops(cfg, B, 1) if cfg.xlstm else 0.0
    else:
        attn = _attn_fwd_flops(cfg, B, S, S, causal=True) * fwd_share
        ssm = (_ssm_fwd_flops(cfg, B, S) if cfg.mamba else 0.0) * fwd_share
        mls = (_mlstm_fwd_flops(cfg, B, S) if cfg.xlstm else 0.0) * fwd_share
        sls = (_slstm_fwd_flops(cfg, B, S) if cfg.xlstm else 0.0) * fwd_share

    total += attn * _attn_layers(cfg)
    total += ssm * _mixer_layers(cfg, "mamba")
    total += mls * _mixer_layers(cfg, "mlstm")
    total += sls * _mixer_layers(cfg, "slstm")

    if cfg.encoder is not None:  # whisper: encoder + cross-attention
        Se = cfg.encoder.num_frames
        enc_attn = _attn_fwd_flops(cfg, B, Se, Se, causal=False) * fwd_share
        total += enc_attn * cfg.encoder.num_layers
        if shape.kind == "decode":
            total += _attn_fwd_flops(cfg, B, 1, Se, causal=False) * cfg.num_layers
        else:
            total += _attn_fwd_flops(cfg, B, S, Se, causal=False) * fwd_share * cfg.num_layers
    return float(total)
