"""STREAM kernels (copy / scale / add / triad) — HBM bandwidth probes.

Arrays are processed as (rows, 128) lanes; block rows sized so each tile is
a few MiB of VMEM (default 2048 x 128 x 4 B = 1 MiB per operand). These are
the paper's STREAM benchmark kernels, unchanged semantics (§3.4): the metric
is bytes moved / time, normalized per memory bank in the paper and per HBM
stack here.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.gemm import LANE, SUBLANE, fit_block


def _as2d(x):
    assert x.size % LANE == 0, x.shape
    return x.reshape(-1, LANE)


def _copy_kernel(a_ref, o_ref):
    o_ref[...] = a_ref[...]


def _scale_kernel(c_ref, o_ref, *, alpha):
    o_ref[...] = (alpha * c_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _add_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = (a_ref[...].astype(jnp.float32)
                  + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _triad_kernel(b_ref, c_ref, o_ref, *, alpha):
    o_ref[...] = (b_ref[...].astype(jnp.float32)
                  + alpha * c_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _run(kernel, args, out_dtype, *, block_rows=2048, interpret=False):
    x0 = _as2d(args[0])
    rows = x0.shape[0]
    br = fit_block(rows, block_rows, 1 if interpret else SUBLANE)
    grid = (rows // br,)
    spec = pl.BlockSpec((br, LANE), lambda i: (i, 0))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec] * len(args),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x0.shape, out_dtype),
        interpret=interpret,
    )(*[_as2d(a) for a in args])
    return out.reshape(args[0].shape)


def stream_copy(a, *, interpret=False):
    return _run(_copy_kernel, (a,), a.dtype, interpret=interpret)


def stream_scale(c, alpha: float, *, interpret=False):
    return _run(partial(_scale_kernel, alpha=alpha), (c,), c.dtype,
                interpret=interpret)


def stream_add(a, b, *, interpret=False):
    return _run(_add_kernel, (a, b), a.dtype, interpret=interpret)


def stream_triad(b, c, alpha: float, *, interpret=False):
    return _run(partial(_triad_kernel, alpha=alpha), (b, c), b.dtype,
                interpret=interpret)
