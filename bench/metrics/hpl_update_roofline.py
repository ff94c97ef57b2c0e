"""Share of its roofline HPL's trailing update reaches, read by phase: the
update work HPL requires on each device (what ``hpl_gemm_roofline``
counts: finished panels' tiles left out, whatever the kernel launches), at
the bf16 compute peak, over the device time of the program's scopes
``hpl.update`` and ``hpl.strip`` (the lookahead's strip GEMMs), summed over
devices, whatever kernel or fusion implements them."""
from bench import scopes

PHASES = ("hpl.update", "hpl.strip")


def compute(ctx):
    sc = scopes.scoped(ctx)
    if sc is None or ctx.calls == 0:
        return None
    busy = sum(scopes.phase_ns(sc, PHASES).values())
    if busy == 0:
        return None
    cell = ctx.cell
    n, b, pg = cell.size.n, cell.size.b, cell.size.grid
    need = sum(cell.driver.required_update_flops(n, b, pg, r, c)
               for r in range(pg) for c in range(pg))
    least_s = ctx.calls * need / ctx.peaks.flops
    return 100.0 * least_s / (busy / 1e9)
