"""Whole-step share of the chips' peak: useful GEMM FLOPs completed in the
traced window over (window x chips x peak bf16 FLOP/s)."""


def read(ctx):
    if "useful_flops" not in ctx.work:
        return None
    return 100.0 * ctx.work["useful_flops"] / (
        ctx.trace.window_s * ctx.chips * ctx.peaks.bf16_flops)
