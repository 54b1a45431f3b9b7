"""Share of the traced window in which a collective runs on a chip and no
other operation does, averaged over the chips
(``bench/lib/trace.collective_exposed_share``).  Nothing to read, and no
value, where the trace holds no collective."""
from bench.lib import trace as tr


def read(ctx):
    if not tr.has_collectives(ctx.trace):
        return None
    return 100.0 * tr.collective_exposed_share(ctx.trace)
