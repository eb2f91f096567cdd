"""Share of the traced training window in which no operation ran on the
device (1 minus the union of the device's op intervals over the window)."""
import trace_events as te


def read(ctx):
    lo, hi = ctx.get("window", (0.0, 0.0))
    if ctx.get("kind") != "train" or hi <= lo or not te.planes(ctx["events"]):
        return None
    return 100.0 * (1.0 - te.busy(ctx["events"], [(lo, hi)])
                    / ((hi - lo) * 1e-9))
