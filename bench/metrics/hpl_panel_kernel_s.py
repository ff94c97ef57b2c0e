"""Device seconds per factorization in the diagonal-block LU and the two
triangular-solve kernels, averaged over devices."""
from bench import trace as tr

KERNELS = r"^(lu_factor_block|trsm_lower_left|trsm_upper_right)(\.|$)"


def compute(ctx):
    ns = tr.op_ns(ctx.trace, KERNELS)
    if ctx.calls == 0 or not any(ns.values()):
        return None
    return sum(ns.values()) / len(ns) / ctx.calls / 1e9
