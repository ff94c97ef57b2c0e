"""Device idle share of the traced window: 1 - (union of device op
intervals) / window, averaged over devices, in percent."""
from bench import trace as tr


def compute(ctx):
    return 100.0 * tr.idle_share(ctx.trace)
