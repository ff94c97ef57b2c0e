"""Share of its roofline the trailing-update GEMM kernel reaches: the
update work HPL requires on each device (finished panels' tiles not
counted, whatever the kernel launches), at the bf16 compute peak (the
update is compute-bound), over the kernel's device time, summed over
devices."""
from bench import trace as tr

KERNEL = r"^gemm_update(\.|$)"


def compute(ctx):
    cell = ctx.cell
    ns = tr.op_ns(ctx.trace, KERNEL)
    busy = sum(ns.values())
    if busy == 0 or ctx.calls == 0:
        return None
    n, b, pg = cell.size.n, cell.size.b, cell.size.grid
    need = sum(cell.driver.required_update_flops(n, b, pg, r, c)
               for r in range(pg) for c in range(pg))
    least_s = ctx.calls * need / ctx.peaks.flops
    return 100.0 * least_s / (busy / 1e9)
