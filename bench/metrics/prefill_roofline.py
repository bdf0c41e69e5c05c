"""Share of its roofline the compiled prefill program reaches: the least time
the chip needs for one prefill call (``bench/work/``) over that program's
device time per call in the trace. The program is the XLA module
``jit_prefill`` (``ServeEngine.warmup`` jits a function named ``prefill``)."""
from bench.metrics._window import least, roofline

MODULE = "jit_prefill"


def read(ctx):
    return roofline(ctx, MODULE, 1, least(ctx.work[0], ctx.peak))
