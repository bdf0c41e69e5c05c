"""The device's ``peak_bytes_in_use`` after the window, in GB (1e9 bytes)."""


def read(ctx):
    return None if ctx.memory_peak_bytes is None else ctx.memory_peak_bytes / 1e9
