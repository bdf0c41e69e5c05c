"""Mean host time of one prefill call in the window: ``EngineStats.prefill_s``
(the engine's span, ending in ``block_until_ready``) over the calls."""


def read(ctx):
    if not ctx.window.cycles:
        return None
    return 1000.0 * ctx.stats["prefill_s"] / ctx.window.cycles
