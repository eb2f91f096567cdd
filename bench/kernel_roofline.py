"""Roofline share of one kernel over the decode steps of a traced window.

The least time the chip could take for the kernel's calls is, call by
call, the larger of their FLOPs over the bf16 peak and their bytes over
the HBM bandwidth; the share is that least time over the device time of
the kernel's events (``trace_events.kernel_of``) that start inside the
``engine.decode_step`` spans.
"""
import trace_events as te


def share(ctx, kernel: str, calls_per_step) -> float | None:
    """``calls_per_step(contexts)`` gives the (FLOPs, bytes) of each of
    the kernel's calls in one decode step."""
    if ctx.get("kind") != "serve" or not ctx.get("decode_contexts"):
        return None
    lo, hi = ctx["window"]
    spans = [(s, e) for s, e in te.spans(ctx["events"], "engine.decode_step")
             if lo <= s < hi]
    steps = ctx["decode_contexts"][: len(spans)]
    if not spans or len(steps) < len(spans):
        return None
    evs = [e for e in te.inside(te.ops(ctx["events"]), spans)
           if te.kernel_of(e) == kernel]
    kernel_s = sum(e[4] for e in evs) / max(len({e[0] for e in evs}), 1) * 1e-9
    if kernel_s <= 0:
        return None
    pk = ctx["peaks"]
    least = sum(max(f / pk["bf16_flops"], b / pk["hbm_bytes_s"])
                for c in steps for f, b in calls_per_step(c))
    return 100.0 * least / kernel_s
