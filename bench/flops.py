"""What every configuration's work count shares: the bytes of a served
dtype, and the least time the chip needs for a given count.

A configuration's operations and bytes per prefill call and decode step are
in ``bench/work/<reference>.py``, one module per architecture, each with
``param_count``, ``kv_bytes_per_token``, ``prefill`` and ``decode_step``.
"""
from __future__ import annotations

from typing import Any, Dict

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def least_time(flops: float, nbytes: float, peak: Dict[str, Any]) -> float:
    """Seconds the chip needs at best: the larger of the compute and the
    memory bound."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
