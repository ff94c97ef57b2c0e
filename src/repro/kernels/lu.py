"""Blocked-LU building blocks (Pallas TPU): the paper's four HPL kernels.

Paper §2.3/Fig. 4 decomposes each iteration into: LU (diagonal block
factorization), Top (U panel via lower-triangular solve), Left (L panel via
upper-triangular solve, transposed on the fly), and the inner matrix
multiplications (see kernels/gemm.py). No pivoting (HPL-AI ruleset,
diagonally-dominant A).

The diagonal factorization and the triangular solves are sequential over the
block dimension — that is inherent to LU — but they touch O(b^2) data while
the trailing GEMMs touch O(n^2) per iteration, so these kernels sit off the
critical roofline for large n (paper Fig. 13: performance converges to the
matmul bound).

Every step is written as full-tile masked VPU arithmetic on a float32 VMEM
scratch: row ``k`` and column ``k`` are extracted with 2-D iota masks and a
reduction (adding zeros is exact, so the extraction is too), and updates are
masked selects. Mosaic has no dynamic slice on values and no dynamic lane
offsets, so this is the form the TPU compiler accepts; it also keeps every
product in plain float32 rather than in the MXU's bf16 passes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gemm import LANE, SUBLANE, fit_block


def _iota(shape, dim):
    return lax.broadcasted_iota(jnp.int32, shape, dim)


def _pick(x, mask, axis):
    """The entries of ``x`` where ``mask`` holds, summed along ``axis``
    (keepdims): with one true entry per line this is an exact gather."""
    return jnp.sum(jnp.where(mask, x, 0.0), axis=axis, keepdims=True)


# ---------------------------------------------------------------------------
# diagonal block: in-place LU (Doolittle, unit lower diagonal)
# ---------------------------------------------------------------------------


def _lu_block_kernel(a_ref, o_ref, w_ref):
    n = a_ref.shape[0]
    rows, cols = _iota((n, n), 0), _iota((n, n), 1)
    lane = _iota((1, n), 1)
    w_ref[...] = a_ref[...].astype(jnp.float32)

    def body(k, carry):
        a = w_ref[...]
        row = _pick(a, rows == k, 0)                # (1, n): a[k, :]
        col = _pick(a, cols == k, 1)                # (n, 1): a[:, k]
        pivot = _pick(row, lane == k, 1)            # (1, 1): a[k, k]
        l = col / pivot                             # L column (rows > k)
        below = rows > k
        a = a - jnp.where(below & (cols > k), l * row, 0.0)
        w_ref[...] = jnp.where(below & (cols == k), l, a)
        return carry

    lax.fori_loop(0, n, body, 0)
    o_ref[...] = w_ref[...].astype(o_ref.dtype)


def lu_factor_block(a: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """LU-factorize one (b, b) block, returning L\\U packed (unit L diag)."""
    n = a.shape[0]
    assert a.shape == (n, n)
    return pl.pallas_call(
        _lu_block_kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec((n, n), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((n, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        name="lu_factor_block",
        interpret=interpret,
    )(a)


# ---------------------------------------------------------------------------
# panel solves
# ---------------------------------------------------------------------------


def _trsm_lower_kernel(lu_ref, b_ref, o_ref, x_ref):
    """Solve L X = B where L is unit-lower from packed LU. One grid cell per
    panel block (the paper's Top kernel: U_kj = L_kk^{-1} A_kj).
    Column-oriented forward substitution: once row j of X is final, its
    contribution L[i, j] X[j, :] leaves every row i > j."""
    n = lu_ref.shape[0]
    lrows, lcols = _iota((n, n), 0), _iota((n, n), 1)
    xrows = _iota(b_ref.shape, 0)
    x_ref[...] = b_ref[...].astype(jnp.float32)

    def body(j, carry):
        l = lu_ref[...].astype(jnp.float32)
        lcol = _pick(l, (lcols == j) & (lrows > j), 1)   # (n, 1): L[i>j, j]
        x = x_ref[...]
        xj = _pick(x, xrows == j, 0)                     # (1, w): X[j, :]
        x_ref[...] = x - lcol * xj
        return carry

    lax.fori_loop(0, n, body, 0)
    o_ref[...] = x_ref[...].astype(o_ref.dtype)


def trsm_lower_left(lu: jnp.ndarray, b: jnp.ndarray, *, bn: int = 256,
                    interpret: bool = False) -> jnp.ndarray:
    """X = L^{-1} B for packed-LU ``lu`` (b, b) and panel ``b`` (b, N)."""
    n = lu.shape[0]
    N = b.shape[1]
    bn = fit_block(N, bn, 1 if interpret else LANE)
    return pl.pallas_call(
        _trsm_lower_kernel,
        grid=(N // bn,),
        in_specs=[
            pl.BlockSpec((n, n), lambda j: (0, 0)),
            pl.BlockSpec((n, bn), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((n, bn), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct(b.shape, b.dtype),
        scratch_shapes=[pltpu.VMEM((n, bn), jnp.float32)],
        name="trsm_lower_left",
        interpret=interpret,
    )(lu, b)


def _trsm_upper_kernel(lu_ref, b_ref, o_ref, x_ref):
    """Solve X U = B for U upper from packed LU (the paper's Left kernel:
    L_ik = A_ik U_kk^{-1}). Column j of X is final once divided by U[j, j];
    it then leaves every column i > j through U[j, i]."""
    n = lu_ref.shape[0]
    urows = _iota((n, n), 0)
    lane = _iota((1, n), 1)
    xcols = _iota(b_ref.shape, 1)
    x_ref[...] = b_ref[...].astype(jnp.float32)

    def body(j, carry):
        urow = _pick(lu_ref[...].astype(jnp.float32), urows == j, 0)  # U[j, :]
        ujj = _pick(urow, lane == j, 1)                                # (1, 1)
        x = x_ref[...]
        xj = _pick(x, xcols == j, 1) / ujj                             # (bm, 1)
        x = x - xj * jnp.where(lane > j, urow, 0.0)
        x_ref[...] = jnp.where(xcols == j, xj, x)
        return carry

    lax.fori_loop(0, n, body, 0)
    o_ref[...] = x_ref[...].astype(o_ref.dtype)


def trsm_upper_right(lu: jnp.ndarray, b: jnp.ndarray, *, bm: int = 256,
                     interpret: bool = False) -> jnp.ndarray:
    """X = B U^{-1} for packed-LU ``lu`` (b, b) and panel ``b`` (M, b)."""
    n = lu.shape[0]
    M = b.shape[0]
    bm = fit_block(M, bm, 1 if interpret else SUBLANE)
    return pl.pallas_call(
        _trsm_upper_kernel,
        grid=(M // bm,),
        in_specs=[
            pl.BlockSpec((n, n), lambda i: (0, 0)),
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(b.shape, b.dtype),
        scratch_shapes=[pltpu.VMEM((bm, n), jnp.float32)],
        name="trsm_upper_right",
        interpret=interpret,
    )(lu, b)
