"""Share of the decode steps' time (their ``engine.decode_step`` spans in
the traced window) in which no operation ran on the device."""
import trace_events as te


def read(ctx):
    if ctx.get("kind") != "serve" or not te.planes(ctx["events"]):
        return None
    lo, hi = ctx["window"]
    spans = [(s, e) for s, e in te.spans(ctx["events"], "engine.decode_step")
             if lo <= s < hi]
    if not spans:
        return None
    total = sum(e - s for s, e in spans) * 1e-9
    return 100.0 * (1.0 - te.busy(ctx["events"], spans) / total)
