"""Device seconds per call in PTRANS's exchange, read by phase: the ops
whose innermost scope is the engine's for the callsite ``ptrans.exchange``
(``comm.grid_transpose:ptrans.exchange``, ``comm.pipelined:...``), so a
transpose-add run as the pipeline's consumer is left out, averaged over
devices. It reads 0.0 once the calls ran and the scope holds no op (an
exchange that moves nothing on the device)."""
from bench import scopes

PHASES = ("comm.*:ptrans.exchange",)


def compute(ctx):
    sc = scopes.scoped(ctx)
    if sc is None or ctx.calls == 0:
        return None
    ns = scopes.phase_ns(sc, PHASES)
    return sum(ns.values()) / len(ns) / ctx.calls / 1e9
