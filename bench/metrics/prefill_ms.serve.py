"""Device time of one prefill: the mean duration of the program run by
``Server``'s jitted prefill (``jit_prefill``) in the traced window."""
import numpy as np

from bench.lib import trace as tr

PROGRAM = r"^jit_prefill\b"


def read(ctx):
    runs = tr.module_durations(ctx.trace, PROGRAM)
    return 1e3 * float(np.mean(runs)) if runs else None
