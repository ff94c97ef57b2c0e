"""Device seconds per call in the collective engine's exchange (the
collective ops of ``grid_transpose``), averaged over devices. On a 1x1 grid
the exchange is a collective-permute of the whole local A onto the same
chip, a device-side copy."""
from bench import trace as tr


def compute(ctx):
    ns = tr.op_ns(ctx.trace, tr.COLLECTIVE_OPS)
    if ctx.calls == 0 or not any(ns.values()):
        return None
    return sum(ns.values()) / len(ns) / ctx.calls / 1e9
