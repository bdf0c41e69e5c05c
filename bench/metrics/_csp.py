"""Time of the program's CSP codec spans per handoff in the traced cycles."""

SERIALIZE = "truffle.csp.serialize"


def per_handoff_ms(ctx, names):
    """Seconds of the ``truffle.csp.<name>`` spans inside the traced window
    per ``truffle.csp.serialize``, in ms; None where the trace has none."""
    spans = ctx.trace.spans
    handoffs = spans.get(SERIALIZE, (0.0, 0))[1]
    if not handoffs:
        return None
    secs = sum(spans.get(f"truffle.csp.{n}", (0.0, 0))[0] for n in names)
    return 1000.0 * secs / handoffs
