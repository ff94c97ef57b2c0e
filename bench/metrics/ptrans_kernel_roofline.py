"""Share of its roofline the transpose-add kernel reaches: the bytes the
algorithm must move per device and call (read A, read B, write C:
3 m^2 float32 words) at the HBM peak (the kernel is memory-bound), over the
kernel's device time."""
from bench import trace as tr

KERNEL = r"^transpose_add(\.|$)"


def compute(ctx):
    cell = ctx.cell
    ns = tr.op_ns(ctx.trace, KERNEL)
    busy = sum(ns.values())
    if busy == 0 or ctx.calls == 0:
        return None
    least_s = (len(ns) * ctx.calls
               * cell.driver.required_bytes(cell.size.n, cell.size.grid)
               / ctx.peaks.hbm_bw)
    return 100.0 * least_s / (busy / 1e9)
