"""Share of the traced window in which no operation runs on a chip,
averaged over the chips (``bench/lib/trace.idle_share``)."""
from bench.lib import trace as tr


def read(ctx):
    return 100.0 * tr.idle_share(ctx.trace)
