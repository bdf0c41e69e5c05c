"""Arithmetic several per-layer readers share."""
from __future__ import annotations

from bench import flops


def window_mfu(ctx):
    """Model FLOPs of every cycle completed in the measured window over the
    window's length times the chip's peak, in percent."""
    (pf, _), steps = ctx.work
    total = ctx.window.cycles * (pf + sum(f for f, _ in steps))
    return 100.0 * total / (ctx.window.seconds * ctx.peak["bf16_flops_per_s"])


def roofline(ctx, module_prefix: str, calls_per_cycle, least_per_cycle):
    """Least time of the traced calls over their device time, in percent;
    None where the trace has no such module."""
    secs, calls = ctx.trace.module_time(module_prefix)
    if not calls or secs <= 0:
        return None
    least = least_per_cycle * calls / calls_per_cycle
    return 100.0 * least / secs


def least(work, peak):
    return flops.least_time(work[0], work[1], peak)
