"""Mean length of the engine's ``engine.decode_step`` spans (one batched
decode over the slots, sampling included) in the traced window."""
import trace_events as te


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    lo, hi = ctx["window"]
    spans = [(s, e) for s, e in te.spans(ctx["events"], "engine.decode_step")
             if lo <= s < hi]
    if not spans:
        return None
    return sum(e - s for s, e in spans) * 1e-6 / len(spans)
