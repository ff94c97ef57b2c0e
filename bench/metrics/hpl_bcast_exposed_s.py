"""Device seconds per factorization in which only HPL's broadcasts run:
the ops of the engine's scopes for the callsites ``hpl.block`` and
``hpl.panel`` (``comm.<op>:hpl.block``, ``comm.<op>:hpl.panel``), less the
time any other op runs beside them, averaged over devices."""
from bench import scopes

PHASES = ("comm.*:hpl.block", "comm.*:hpl.panel")


def compute(ctx):
    sc = scopes.scoped(ctx)
    if sc is None or ctx.calls == 0:
        return None
    ns = scopes.phase_exposed_ns(sc, PHASES)
    return sum(ns.values()) / len(ns) / ctx.calls / 1e9
