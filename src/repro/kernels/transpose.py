"""Tiled transpose-add kernel: C = B + A^T (the PTRANS inner operation).

The paper's PTRANS kernel (§2.2) streams a block of A into local memory,
reads it back transposed, adds the matching block of B, and writes C. The
TPU version does exactly that per (bi, bj) grid cell: the BlockSpec fetches
A's (j, i) tile and B's (i, j) tile into VMEM; the in-VMEM transpose is a
register-level permutation on the VPU.

Paper Eq. 6 balance: each output tile moves 3 tiles of HBM traffic (read A^T
tile, read B tile, write C tile) — the kernel is HBM-bandwidth-bound, which
is what the PTRANS roofline records.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.gemm import LANE, fit_block


def _transpose_add_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = (b_ref[...].astype(jnp.float32)
                  + a_ref[...].astype(jnp.float32).T).astype(o_ref.dtype)


def transpose_add(a: jnp.ndarray, b: jnp.ndarray, *, block: int = 256,
                  interpret: bool = False) -> jnp.ndarray:
    """C = B + A^T for square-tileable matrices. a: (M, N), b/C: (N, M)."""
    M, N = a.shape
    assert b.shape == (N, M)
    # square tiles (the A tile is read transposed): a common divisor, which
    # a compiled kernel needs lane-aligned unless it spans both dims
    bs = fit_block(math.gcd(M, N), block, 1 if interpret else LANE)
    if not (interpret or bs % LANE == 0 or bs == M == N):
        raise ValueError(f"transpose_add: no lane-aligned square tile for "
                         f"{a.shape}")
    grid = (N // bs, M // bs)  # output tile (i, j) of C
    return pl.pallas_call(
        _transpose_add_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bs, bs), lambda i, j: (j, i)),  # A tile transposed
            pl.BlockSpec((bs, bs), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bs, bs), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N, M), b.dtype),
        name="transpose_add",
        interpret=interpret,
    )(a, b)
