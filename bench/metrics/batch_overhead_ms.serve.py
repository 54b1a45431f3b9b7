"""Chip idle per batch outside decoding: the seconds in which no
operation runs on a chip inside the ``serve.generate`` spans that lie
wholly in the window but outside their ``serve.decode`` spans, per
``serve.generate`` span, averaged over the chips.  Batch preparation,
the prefill's dispatch, the first token and collecting the outputs."""
import numpy as np

from bench.lib import spans


def read(ctx):
    t = ctx.trace
    batches = spans.within(t, "serve.generate")
    if not batches:
        return None
    decode = spans.inside(spans.within(t, "serve.decode"), batches)
    idle = [spans.idle_in(t, c, batches) - spans.idle_in(t, c, decode)
            for c in t.chips]
    return 1e3 * float(np.mean(idle)) / len(batches)
