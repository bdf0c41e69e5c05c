"""Mean host time of one decode step inside ``step_batch`` in the window:
``EngineStats.decode_s`` over the steps it covers. Each batch's span also
holds the host sync on its prefill token, so steps = tokens out / batch -
batches."""


def read(ctx):
    steps = ctx.stats["tokens_out"] / ctx.mix["clients"] - ctx.window.cycles
    if steps <= 0:
        return None
    return 1000.0 * ctx.stats["decode_s"] / steps
