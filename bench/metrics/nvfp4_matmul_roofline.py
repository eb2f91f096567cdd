"""Roofline share of the packed NVFP4 GEMM (``repro.nvfp4_matmul``) in the
decode steps: each layer's five GEMMs over all the engine's slots (the
decode step multiplies every slot's row), bytes of the packed codes,
block scales, x and the output (``flops.gemm_call``)."""
import flops
import kernel_roofline


def read(ctx):
    def calls(contexts):
        return [flops.gemm_call(ctx["slots"], k, n)
                for k, n in flops.layer_gemms(ctx["dims"]).values()
                ] * ctx["dims"]["n_layers"]
    return kernel_roofline.share(ctx, "nvfp4_matmul", calls)
