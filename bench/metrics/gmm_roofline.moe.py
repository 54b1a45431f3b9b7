"""Share of the grouped expert GEMM's roofline: for each held expert's
three GEMMs in every layer run in the traced window, the least time its
routed rows' FLOPs and its operand and output bytes take at the chips'
peaks (``bench/lib/counts.gemm_min_s``, from the ``expert_rows`` the
driver counted; an expert with no rows needs none), summed, over the
device seconds of the grouped GEMM's operations (``bench/lib/experts``)."""
from bench.lib.experts import gmm_seconds


def read(ctx):
    if "gmm_min_s" not in ctx.work:
        return None
    seconds = gmm_seconds(ctx.trace)
    return 100.0 * ctx.work["gmm_min_s"] / seconds if seconds > 0 else None
