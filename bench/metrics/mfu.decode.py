"""Model FLOP/s of the decode steps over the chip's bf16 peak: two FLOPs
per matmul weight for each active token plus attention over its live
context (``flops.decode_step``), summed over the traced window's decode
steps, over the time of their ``engine.decode_step`` spans."""
import flops
import trace_events as te


def read(ctx):
    if ctx.get("kind") != "serve" or not ctx.get("decode_contexts"):
        return None
    lo, hi = ctx["window"]
    spans = [(s, e) for s, e in te.spans(ctx["events"], "engine.decode_step")
             if lo <= s < hi]
    steps = ctx["decode_contexts"][: len(spans)]
    if not spans or len(steps) < len(spans):
        return None
    work = sum(flops.decode_step(ctx["dims"], c) for c in steps)
    secs = sum(e - s for s, e in spans) * 1e-9
    return 100.0 * work / secs / ctx["peaks"]["bf16_flops"]
