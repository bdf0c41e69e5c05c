"""Device-to-host copy of the KV cache per CSP handoff: the
``truffle.csp.d2h`` spans (one per leaf, inside ``serialize``) per
``truffle.csp.serialize`` in the traced cycles."""
from bench.metrics._csp import per_handoff_ms


def read(ctx):
    return per_handoff_ms(ctx, ("d2h",))
