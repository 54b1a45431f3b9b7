"""Chip idle per decode step: the seconds in which no operation runs on a
chip inside the ``serve.decode`` spans that lie wholly in the window,
over the ``serve.decode_step`` spans inside them, averaged over the
chips.  The host's share of each token's latency: the key split, the
step's dispatch and the sync between steps."""
import numpy as np

from bench.lib import spans


def read(ctx):
    t = ctx.trace
    decode = spans.within(t, "serve.decode")
    steps = len(spans.inside(spans.within(t, "serve.decode_step"), decode))
    if not steps:
        return None
    return 1e3 * float(np.mean([spans.idle_in(t, c, decode)
                                for c in t.chips])) / steps
