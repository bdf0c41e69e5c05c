"""Share of its roofline the compiled decode step reaches: the least time of
the traced steps (``bench/work/``, each step over its filled cache slots)
over their device time. ``ServeEngine.warmup`` jits the step as a lambda,
whose XLA module is ``jit__lambda``."""
from bench.metrics._window import least, roofline

MODULE = "jit__lambda"


def read(ctx):
    steps = ctx.work[1]
    if not steps:
        return None
    return roofline(ctx, MODULE, len(steps), sum(least(s, ctx.peak) for s in steps))
