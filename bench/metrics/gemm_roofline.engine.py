"""Share of the GEMMs' roofline: for each GEMM run in the traced window,
the least time its useful FLOPs and its operand and output bytes take at
the chips' peaks (``bench/lib/counts.gemm_min_s``), summed, over the
device's busy time (averaged over the chips).  It reads the same work
whatever multiplies: the Pallas kernel, XLA's dot, padded or not."""
from bench.lib import trace as tr


def read(ctx):
    busy = tr.busy_s(ctx.trace)
    if "gemm_min_s" not in ctx.work or busy <= 0:
        return None
    return 100.0 * ctx.work["gemm_min_s"] / busy
