"""Share of the HBM bandwidth a decode step would use if it read only what
it must: every weight it multiplies plus the keys and values of the
tokens actually held (not the ``max_seq`` slots), over the mean device
time of the decode-step program (``jit_step``), over the peak."""
import numpy as np

from bench.lib import trace as tr

PROGRAM = r"^jit_step\b"


def read(ctx):
    runs = tr.module_durations(ctx.trace, PROGRAM)
    if not runs or "decode_weight_bytes" not in ctx.work:
        return None
    need = ctx.work["decode_weight_bytes"] + ctx.work["decode_kv_bytes"]
    return 100.0 * need / float(np.mean(runs)) / ctx.peaks.hbm_bytes_per_s
