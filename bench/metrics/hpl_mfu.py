"""The whole factorization's share of the chip's peak, on device time: HPL's
nominal work, 2/3 N^3 per factorization, for the factorizations completed
in the traced window, over the device seconds in which an op of theirs ran
(the union of op intervals, averaged over devices), over chips x the
published bf16 peak (bench/peaks.py). It differs from ``hpl_gflops`` by
leaving out the device's idle time. The work is float32 at
Precision.HIGHEST, which the MXU runs in several bf16 passes, so 100% is out
of its reach."""
from bench import trace as tr


def compute(ctx):
    cell = ctx.cell
    busy = tr.busy_ns(ctx.trace)
    busy_s = sum(busy.values()) / len(busy) / 1e9
    if ctx.calls == 0 or busy_s <= 0:
        return None
    rate = ctx.calls * cell.driver.nominal_flops(cell.size.n) / busy_s
    return 100.0 * rate / (cell.chips * ctx.peaks.flops)
