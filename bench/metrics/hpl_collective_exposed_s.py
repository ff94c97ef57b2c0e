"""Per factorization, the device seconds of collective ops during which no
other op runs on that device, averaged over devices. Nothing to read on a
single chip, where the engine's broadcasts are no-ops."""
from bench import trace as tr


def compute(ctx):
    if ctx.calls == 0 or ctx.cell.chips == 1:
        return None
    ns = tr.exposed_ns(ctx.trace, tr.COLLECTIVE_OPS)
    return sum(ns.values()) / len(ns) / ctx.calls / 1e9
