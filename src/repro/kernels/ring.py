"""Fused local step of the ring reduce-scatter / all-gather allreduce.

The bandwidth-optimal ring allreduce (the engine's ``rs_ag`` schedule) moves
one 1/n-sized chunk per hop: the reduce-scatter half *adds* the received
chunk into the local accumulator, the all-gather half *copies* it into the
output slot. On TPU the add is the fusion opportunity — receive buffer and
accumulator stream through VMEM once, instead of a ppermute output
materializing in HBM and a separate add reading it back. ``ring_add_step``
is that fused add as a Pallas kernel; ``fused_chunk_add`` is the
shape-tolerant wrapper the engine calls per hop.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.gemm import LANE, SUBLANE, fit_block
from repro.kernels.ops import interpret_mode


def _add_kernel(acc_ref, recv_ref, o_ref):
    o_ref[...] = (acc_ref[...].astype(jnp.float32)
                  + recv_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def ring_add_step(acc: jnp.ndarray, recv: jnp.ndarray, *, block_rows: int = 512,
                  interpret: bool = False) -> jnp.ndarray:
    """acc + recv over (rows, LANE)-shaped chunks, one VMEM pass."""
    assert acc.shape == recv.shape and acc.ndim == 2, (acc.shape, recv.shape)
    rows = acc.shape[0]
    br = fit_block(rows, block_rows, 1 if interpret else SUBLANE)
    spec = pl.BlockSpec((br, LANE), lambda i: (i, 0))
    return pl.pallas_call(
        _add_kernel,
        grid=(rows // br,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype),
        interpret=interpret,
    )(acc, recv)


def fused_chunk_add(acc: jnp.ndarray, recv: jnp.ndarray) -> jnp.ndarray:
    """Fused accumulate for one ring hop. Any chunk shape: the flattened
    chunk is zero-padded to whole (8, 128) tiles, added in the kernel, and
    the padding cut off again (elementwise, so the sum is unchanged)."""
    flat_acc, flat_recv = acc.reshape(-1), recv.reshape(-1)
    if flat_acc.size == 0:
        return acc
    pad = (-flat_acc.size) % (SUBLANE * LANE)
    if pad:
        flat_acc = jnp.pad(flat_acc, (0, pad))
        flat_recv = jnp.pad(flat_recv, (0, pad))
    out = ring_add_step(flat_acc.reshape(-1, LANE),
                        flat_recv.reshape(-1, LANE),
                        interpret=interpret_mode())
    return out.reshape(-1)[:acc.size].reshape(acc.shape)
