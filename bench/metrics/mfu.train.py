"""Model FLOP/s of the QAD step over the chip's bf16 peak, in the traced
window: the teacher's forward and the student's forward and backward per
token (``flops.qad_step_per_token``, recomputation not counted), times the
tokens of the steps completed in the window, over the window's length on
the profiler's clock."""
import flops


def read(ctx):
    lo, hi = ctx.get("window", (0.0, 0.0))
    if ctx.get("kind") != "train" or hi <= lo or not ctx.get("tokens"):
        return None
    work = (flops.qad_step_per_token(ctx["dims"], ctx["seq_len"])
            * ctx["tokens"])
    return 100.0 * work / ((hi - lo) * 1e-9) / ctx["peaks"]["bf16_flops"]
