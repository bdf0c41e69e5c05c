"""Length of one CSP payload (the serialized KV cache), a count."""


def read(ctx):
    vals = [r.payload_bytes for r in ctx.window.records if r.payload_bytes]
    return float(vals[0]) if vals else None
