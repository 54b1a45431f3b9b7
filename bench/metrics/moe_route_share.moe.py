"""Share of the device's busy time spent around the expert GEMMs: 100 x
(busy - the grouped GEMM's operation seconds) / busy, averaged over the
chips.  Routing, dispatch (sorting and gathering the rows) and combine
(the gate-weighted scatter back to each token)."""
from bench.lib import trace as tr
from bench.lib.experts import gmm_seconds


def read(ctx):
    busy = tr.busy_s(ctx.trace)
    seconds = gmm_seconds(ctx.trace)
    if seconds <= 0 or busy <= 0:
        return None
    return 100.0 * (busy - seconds) / busy
