"""Model FLOPs completed in the measured window over the window's length
times the chip's bf16 peak: the whole step's share of the chip, which bounds
every kernel's gain on the end-to-end metric it moves. Reads every
``mfu.<part>`` name."""
from bench.metrics._window import window_mfu


def read(ctx):
    return window_mfu(ctx)
