"""The engine's compile in set-up: ``EngineStats.compile_s``, the
``truffle.engine.warmup`` span's time over every warm-up of the run (from the
persistent cache once a checkout has compiled)."""


def read(ctx):
    secs = ctx.setup["compile_s"]
    return 1000.0 * secs if secs > 0 else None
