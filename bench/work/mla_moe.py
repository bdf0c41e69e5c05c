"""Operations and bytes a decoder with multi-head latent attention, leading
dense layers and one chip's share of routed experts plus shared experts
(``bench/reference/mla_moe.py``) needs for one prefill call and one decode
step, from the configuration's shapes alone.

It counts what the algorithm needs, not what a given implementation does,
in the cheaper form of each call. Prefill expands K and V from the latent
(R * H * (nope + v) per token) and attends with them over S(S+1)/2 causal
pairs. Decode attends in the latent space (absorbed: q_nope through the
key half of Wkv_b, the attention output through its value half) over the
``kv_len`` filled cache slots. The output head runs over the last position
only in prefill. Every weight is read once per call, every held expert
included, and the latent cache is written once and read once.

Routed experts: each token's top-k choices fall on the held experts in the
share ``top_k * held / router_experts`` of the uniform routing the random
router gives on average; the FLOPs of the routed experts use that share, not
the routing a given batch drew. A batch that loads the held experts more or
less than that moves the device time, not the count.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

from bench.flops import BYTES


def _sizes(c: Dict[str, Any]):
    d, H = c["d_model"], c["num_heads"]
    nope, rope = c["mla.qk_nope_head_dim"], c["mla.qk_rope_head_dim"]
    R, vd = c["mla.kv_lora_rank"], c["mla.v_head_dim"]
    f, E, Er = c["moe.d_expert"], c["moe.num_experts"], c["moe.router_experts"]
    s = {
        "q": d * H * (nope + rope), "kv_a": d * (R + rope),
        "kv_b": R * H * (nope + vd), "o": H * vd * d,
        "layer_other": R + 2 * d,                     # kv_norm and two RMSNorms
        "dense": 3 * d * c["d_ff"],
        "router": d * Er,
        "expert": 3 * d * f,
        "shared": 3 * d * f * c["moe.num_shared"],
        "held": E,
        "routed_share": c["moe.top_k"] * E / Er,      # held experts a token uses
        "head": c["vocab_size"] * d,
        "n_lead": c["leading_dense_layers"],
        "n_moe": c["num_layers"] - c["leading_dense_layers"],
    }
    s["attn"] = s["q"] + s["kv_a"] + s["kv_b"] + s["o"]
    s["moe"] = s["router"] + E * s["expert"] + s["shared"]
    return s


def param_count(c: Dict[str, Any]) -> int:
    s = _sizes(c)
    per = s["attn"] + s["layer_other"]
    tables = s["head"] if c["tie_embeddings"] else 2 * s["head"]
    return (s["n_lead"] * (per + s["dense"]) + s["n_moe"] * (per + s["moe"])
            + tables + c["d_model"])


def kv_bytes_per_token(c: Dict[str, Any]) -> int:
    return (c["num_layers"] * (c["mla.kv_lora_rank"] + c["mla.qk_rope_head_dim"])
            * BYTES[c["dtype"]])


def _weight_read_bytes(c: Dict[str, Any]) -> int:
    """Every parameter but the token table, of which a call reads its rows."""
    s = _sizes(c)
    table = 0 if c["tie_embeddings"] else s["head"]
    return (param_count(c) - table) * BYTES[c["dtype"]]


def _ffn_flops_per_token(s) -> Tuple[float, float]:
    """(dense layer, MoE layer) FLOPs per token, routed experts at the
    uniform share."""
    moe = s["router"] + s["shared"] + s["routed_share"] * s["expert"]
    return 2.0 * s["dense"], 2.0 * moe


def prefill(c: Dict[str, Any], batch: int, seq: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one prefill call over ``batch`` prompts of ``seq``."""
    s = _sizes(c)
    L, H = c["num_layers"], c["num_heads"]
    qk = c["mla.qk_nope_head_dim"] + c["mla.qk_rope_head_dim"]
    tokens = batch * seq
    pairs = batch * seq * (seq + 1) / 2
    dense, moe = _ffn_flops_per_token(s)
    flops = (tokens * (2.0 * L * s["attn"] + s["n_lead"] * dense + s["n_moe"] * moe)
             + L * 2.0 * pairs * H * (qk + c["mla.v_head_dim"])
             + 2.0 * s["head"] * batch)
    nbytes = (_weight_read_bytes(c) + tokens * c["d_model"] * BYTES[c["dtype"]]
              + tokens * kv_bytes_per_token(c) + batch * c["vocab_size"] * 4)
    return flops, float(nbytes)


def decode_step(c: Dict[str, Any], batch: int, kv_len: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step: ``batch`` new tokens, each attending
    over ``kv_len`` latent cache slots (its own included)."""
    s = _sizes(c)
    L, H = c["num_layers"], c["num_heads"]
    R, rope = c["mla.kv_lora_rank"], c["mla.qk_rope_head_dim"]
    absorb = H * R * (c["mla.qk_nope_head_dim"] + c["mla.v_head_dim"])
    proj = s["q"] + s["kv_a"] + s["o"] + absorb
    dense, moe = _ffn_flops_per_token(s)
    flops = (batch * (2.0 * L * proj + s["n_lead"] * dense + s["n_moe"] * moe
                      + 2.0 * s["head"])
             + L * 2.0 * batch * kv_len * H * ((R + rope) + R))
    nbytes = (_weight_read_bytes(c) + batch * kv_len * kv_bytes_per_token(c)
              + batch * c["vocab_size"] * 4)
    return flops, float(nbytes)
