"""Whole-window share of the chip's peak: the model FLOPs of the real
prompt tokens and generated tokens served in the traced window
(``bench/lib/counts.request_flops``: 2 per weight, attention at the held
length) over (window x chips x peak bf16 FLOP/s)."""


def read(ctx):
    if "model_flops" not in ctx.work:
        return None
    return 100.0 * ctx.work["model_flops"] / (
        ctx.trace.window_s * ctx.chips * ctx.peaks.bf16_flops)
