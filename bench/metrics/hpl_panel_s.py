"""Device seconds per factorization in HPL's panel phases, the program's
scopes ``hpl.factor`` (the diagonal-block LU and its slice) and
``hpl.trsm`` (both triangular solves, their masks and the panel slices),
averaged over devices."""
from bench import scopes

PHASES = ("hpl.factor", "hpl.trsm")


def compute(ctx):
    sc = scopes.scoped(ctx)
    if sc is None or ctx.calls == 0:
        return None
    ns = scopes.phase_ns(sc, PHASES)
    return sum(ns.values()) / len(ns) / ctx.calls / 1e9
