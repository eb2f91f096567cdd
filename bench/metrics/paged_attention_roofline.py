"""Roofline share of fused paged attention (``repro.paged_attention``) in
the decode steps: each active slot's query over its live context in every
layer, bytes of the live KV the algorithm needs (``flops
.paged_attention_call``), not the page strip the kernel walks."""
import flops
import kernel_roofline


def read(ctx):
    def calls(contexts):
        one = dict(ctx["dims"], n_layers=1)
        return [flops.paged_attention_call(one, contexts)] \
            * ctx["dims"]["n_layers"]
    return kernel_roofline.share(ctx, "paged_attention", calls)
