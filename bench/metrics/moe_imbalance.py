"""Load of the held experts in the window's decode steps: the busiest held
expert's tokens over the mean held expert's tokens, per decode step and MoE
layer, as a ratio of the window's sums (``EngineStats.moe_busiest`` over
``moe_routed / held``). 1 is an even load; None where the engine counts no
routed tokens (no expert-parallel layer)."""


def read(ctx):
    routed = ctx.stats.get("moe_routed")
    if not routed:
        return None
    return ctx.stats["moe_busiest"] * ctx.c["moe.num_experts"] / routed
