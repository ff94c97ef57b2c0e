"""Blocked MXU matmul kernels (Pallas TPU).

The GEMM is the paper's HPL update-phase workhorse (paper §2.3: "for large
matrices the performance of the implementation is limited by the aggregated
performance of the matrix multiplication kernels"). Block sizes default to
MXU-aligned 256x256x256 bf16 tiles: A-tile (256x256x2 B) + B-tile + fp32
accumulator (256x256x4 B) = 512 KiB working set, comfortably inside the
16 MiB VMEM budget with double buffering.

The paper's two-level blocking (LOCAL_MEM_BLOCK / REGISTER_BLOCK, Table 4)
maps to: level 1 = the BlockSpec HBM->VMEM tile; level 2 = the MXU's native
128x128 systolic tile, which jnp.dot inside the kernel lowers onto.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the TPU's (8, 128) float32 tile: a compiled kernel's block dims are
# multiples of these or span the whole array dim
SUBLANE, LANE = 8, 128


def fit_block(size: int, pref: int, align: int = 1) -> int:
    """Block for one array dim: the whole dim when it is <= ``pref``, else
    the largest divisor of ``size`` that is <= ``pref`` and a multiple of
    ``align`` (block shapes must tile; compiled kernels pass the TPU tile as
    ``align``). Raises ValueError when no such divisor exists."""
    if size <= pref:
        return size
    b = pref - pref % align
    while b >= align:
        if size % b == 0:
            return b
        b -= align
    raise ValueError(f"no block <= {pref} divides {size} in multiples of "
                     f"{align}")


def _dot(a, b):
    """fp32 accumulation; float32 operands at full float32 precision (the
    MXU's multi-pass mode) — the HPL residual and the GEMM check depend on
    it, and Mosaic's default for float32 is not specified."""
    prec = lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jnp.dot(a, b, precision=prec, preferred_element_type=jnp.float32)


def _blocks(M, N, K, bm, bn, bk, interpret):
    sub, lane = (1, 1) if interpret else (SUBLANE, LANE)
    return (fit_block(M, bm, sub), fit_block(N, bn, lane),
            fit_block(K, bk, lane))


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _dot(a_ref[...], b_ref[...])

    @pl.when(pl.program_id(2) == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul(a: jnp.ndarray, b: jnp.ndarray, *, bm: int = 256, bn: int = 256,
           bk: int = 256, out_dtype=None, interpret: bool = False) -> jnp.ndarray:
    """C = A @ B with fp32 accumulation. Shapes must tile evenly."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    bm, bn, bk = _blocks(M, N, K, bm, bn, bk, interpret)
    out_dtype = out_dtype or a.dtype
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        partial(_matmul_kernel, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="matmul",
        interpret=interpret,
    )(a, b)


def _gemm_update_kernel(c_ref, a_ref, b_ref, o_ref, acc_ref, *, nk: int,
                        alpha: float):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = c_ref[...].astype(jnp.float32)

    acc_ref[...] += alpha * _dot(a_ref[...], b_ref[...])

    @pl.when(pl.program_id(2) == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def gemm_update(c: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray, *,
                alpha: float = -1.0, bm: int = 256, bn: int = 256,
                bk: int = 256, interpret: bool = False) -> jnp.ndarray:
    """C <- C + alpha * A @ B (HPL trailing update with alpha = -1).

    The output buffer aliases C (in-place on TPU) — the HPL trailing matrix
    is updated without a second HBM allocation.
    """
    M, K = a.shape
    _, N = b.shape
    assert c.shape == (M, N)
    bm, bn, bk = _blocks(M, N, K, bm, bn, bk, interpret)
    grid = (M // bm, N // bn, K // bk)
    # aliasing is the TPU in-place path; interpret mode implements donation
    # with a defensive whole-buffer copy per grid step (measured, §Perf C3)
    alias = {} if interpret else {0: 0}
    return pl.pallas_call(
        partial(_gemm_update_kernel, nk=grid[2], alpha=alpha),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), c.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        input_output_aliases=alias,
        name="gemm_update",
        interpret=interpret,
    )(c, a, b)
