"""Operations and bytes a dense decoder with grouped-query attention
(``bench/reference/dense_gqa.py``) needs for one prefill call and one decode
step, from the configuration's shapes alone.

The matmul arithmetic follows ``repro.launch.flops`` (copied, so that no
change to the program moves the yardstick) and adds bytes. It counts what
the algorithm needs, not what a given implementation does: causal attention
over S(S+1)/2 query-key pairs, decode attention over the ``kv_len`` filled
cache slots, the output head over the last position only in prefill, every
weight read once per call, the KV cache written once and read once. A kernel
that skips padding or masked work therefore leaves the count unchanged, and
a share of the roofline built on it cannot pass 100% unless the time leaves
out part of the work.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

from bench.flops import BYTES


def _sizes(c: Dict[str, Any]):
    d, f, hd = c["d_model"], c["d_ff"], c["head_dim"]
    nq, nkv = c["num_heads"], c["num_kv_heads"]
    attn = d * nq * hd + 2 * d * nkv * hd + nq * hd * d
    layer_mm = attn + 3 * d * f                          # SwiGLU: gate, up, down
    layer_other = 2 * d                                  # two RMSNorm scales
    if c["qkv_bias"]:
        layer_other += nq * hd + 2 * nkv * hd
    if c["qk_norm"]:
        layer_other += 2 * hd
    head = c["vocab_size"] * d
    return layer_mm, layer_other, head


def param_count(c: Dict[str, Any]) -> int:
    layer_mm, layer_other, head = _sizes(c)
    tables = head if c["tie_embeddings"] else 2 * head
    return c["num_layers"] * (layer_mm + layer_other) + tables + c["d_model"]


def kv_bytes_per_token(c: Dict[str, Any]) -> int:
    return (c["num_layers"] * 2 * c["num_kv_heads"] * c["head_dim"]
            * BYTES[c["dtype"]])


def _weight_read_bytes(c: Dict[str, Any]) -> int:
    layer_mm, layer_other, head = _sizes(c)
    return (c["num_layers"] * (layer_mm + layer_other) + head + c["d_model"]) \
        * BYTES[c["dtype"]]


def prefill(c: Dict[str, Any], batch: int, seq: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one prefill call over ``batch`` prompts of ``seq``."""
    layer_mm, _, head = _sizes(c)
    L, nq, hd = c["num_layers"], c["num_heads"], c["head_dim"]
    tokens = batch * seq
    pairs = batch * seq * (seq + 1) / 2
    flops = (2.0 * L * layer_mm * tokens + 2.0 * head * batch
             + L * 2.0 * pairs * nq * 2 * hd)
    nbytes = (_weight_read_bytes(c) + tokens * c["d_model"] * BYTES[c["dtype"]]
              + tokens * kv_bytes_per_token(c) + batch * c["vocab_size"] * 4)
    return flops, float(nbytes)


def decode_step(c: Dict[str, Any], batch: int, kv_len: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step: ``batch`` new tokens, each attending
    over ``kv_len`` cache slots (its own included)."""
    layer_mm, _, head = _sizes(c)
    L, nq, hd = c["num_layers"], c["num_heads"], c["head_dim"]
    flops = (2.0 * (L * layer_mm + head) * batch
             + L * 2.0 * batch * kv_len * nq * 2 * hd)
    nbytes = (_weight_read_bytes(c) + batch * kv_len * kv_bytes_per_token(c)
              + batch * c["vocab_size"] * 4)
    return flops, float(nbytes)
