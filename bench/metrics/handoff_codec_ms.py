"""The CSP codec's own work per handoff: ``truffle.csp.pack`` (the payload's
one join), ``.unpack`` (header and views) and ``.narrow`` (a cast, only for a
leaf stored in another dtype) per ``truffle.csp.serialize`` in the traced
cycles."""
from bench.metrics._csp import per_handoff_ms


def read(ctx):
    return per_handoff_ms(ctx, ("pack", "unpack", "narrow"))
