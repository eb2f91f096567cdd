"""Time of the engine's ``engine.prefill`` spans that start in the traced
window per 1000 prompt tokens of the requests admitted in it (the window
on the host's clock), so spans and tokens are of the same requests."""
import trace_events as te


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    lo, hi = ctx["window"]
    spans = [(s, e) for s, e in te.spans(ctx["events"], "engine.prefill")
             if lo <= s < hi]
    tokens = sum(n for t, n in ctx.get("prefills", ()))
    if not spans or not tokens:
        return None
    return sum(e - s for s, e in spans) * 1e-6 / (tokens / 1000.0)
