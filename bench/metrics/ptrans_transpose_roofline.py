"""Share of its roofline PTRANS's transpose-add reaches, read by phase: the
bytes the algorithm must move per device and call (read A, read B, write C:
3 m^2 float32 words) at the HBM peak, over the device time of the
program's scope ``ptrans.transpose_add``, whatever implements it."""
from bench import scopes

PHASES = ("ptrans.transpose_add",)


def compute(ctx):
    sc = scopes.scoped(ctx)
    if sc is None or ctx.calls == 0:
        return None
    ns = scopes.phase_ns(sc, PHASES)
    busy = sum(ns.values())
    if busy == 0:
        return None
    cell = ctx.cell
    least_s = (len(ns) * ctx.calls
               * cell.driver.required_bytes(cell.size.n, cell.size.grid)
               / ctx.peaks.hbm_bw)
    return 100.0 * least_s / (busy / 1e9)
