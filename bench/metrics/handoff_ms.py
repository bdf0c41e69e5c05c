"""Median time of the CSP handoff per request in the window: from the
prefill side's ``last_state`` to the restored cache ready on the device
(serialize, Channel on Clock(0), deserialize, device_put)."""
import statistics


def read(ctx):
    vals = [r.handoff_s for r in ctx.window.records if r.handoff_s is not None]
    return 1000.0 * statistics.median(vals) if vals else None
