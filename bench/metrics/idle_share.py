"""Device: the share of the traced window in which no operation ran on the
chip (1 less the union of device-op intervals over the window), in %."""
from bench.lib import trace as tr


def read(ctx):
    if ctx.hi <= ctx.lo:
        return None
    return 100.0 * tr.idle_share(ctx.ops, ctx.lo, ctx.hi)
