"""Mean host time of one decode step outside its token sync in the window:
``EngineStats.decode_host_s`` over ``decode_steps`` (the loop's dispatch,
sampling and bookkeeping; the device runs the step just handed to it
meanwhile)."""


def read(ctx):
    steps = ctx.stats["decode_steps"]
    if steps <= 0:
        return None
    return 1000.0 * ctx.stats["decode_host_s"] / steps
