"""Single-device blocked LU (paper Fig. 13's per-FPGA sweep).

Folded into a thin wrapper over the distributed factorization
(:mod:`repro.core.hpl`) on a 1 x 1 grid: one code path owns the blocked
right-looking algorithm, and the single-device sweep exercises exactly the
kernels and iteration structure the torus runs (all collective schedules
degenerate to identity on 1-rank axes). Used for the matrix-size
performance sweep, for unit tests, and as the measured single-device curve
that feeds the strong-scaling extrapolation model (paper Fig. 15).

Note the fold changes the *measured compute profile*: the distributed
trailing update is a masked full-matrix GEMM every iteration (the
block-cyclic layout interleaves the trailing submatrix, so slicing it out
is impossible for pg > 1 and the 1 x 1 grid inherits that), ~3x the
shrinking-submatrix FLOPs of the old standalone loop. Reported GFLOP/s
(still normalized by ``hpl_flops(n)``) drops accordingly versus pre-fold
artifacts, and the Fig. 15 curve is consistent with how the *distributed*
per-device work actually scales — which is what the extrapolation predicts."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_mesh
from repro.core.hpcc import BenchResult, register, timeit
from repro.core.hpl import (generate_system, make_factorize,
                            normalized_residual, solve_from_lu)
from repro.core.models import hpl_flops


def lu_blocked(a: jnp.ndarray, b: int) -> jnp.ndarray:
    """Blocked LU of (n, n) ``a`` with block size ``b``; returns packed
    L\\U. Thin wrapper: the distributed right-looking factorization on a
    1 x 1 grid (every broadcast is an identity, the trailing update's
    row/column masks reproduce the shrinking-submatrix sweep)."""
    n = a.shape[0]
    mesh = make_mesh((1, 1), ("rows", "cols"))
    fact = make_factorize(mesh, pg=1, nb=n // b, b=b)
    return fact(a[None])[0]


@register("hpl_single")
def run_hpl_single(mesh=None, comm=None, *, n: int = 512, b: int = 64,
                   reps: int = 2, validate: bool = True,
                   schedule: str = "auto") -> BenchResult:
    # single device: no communication — ``schedule`` is accepted so the
    # drivers can pass one flag suite-wide; recorded as "local" in results.
    a, x_true, b_vec = generate_system(n)
    a_dev = jnp.asarray(a)
    fn = jax.jit(partial(lu_blocked, b=b))
    out, t = timeit(fn, a_dev, reps=reps)

    err = 0.0
    if validate:
        x = solve_from_lu(np.asarray(out), b_vec)
        err = normalized_residual(a, x, b_vec)

    return BenchResult(
        name="hpl_single", metric_name="GFLOP/s", metric=hpl_flops(n) / t / 1e9,
        error=err, times={"best": t},
        details={"n": n, "block": b, "schedule": "local"})
