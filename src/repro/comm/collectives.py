"""Distributed primitives — thin compatibility layer over the engine.

The schedule implementations now live in :mod:`repro.comm.engine`, registered
by name (``chain`` / ``native`` / ``staged`` / ``ring2d`` / ``rs_ag``) and
selected through :class:`repro.comm.engine.CollectiveEngine`. The keyword
functions here preserve the original ad-hoc ``(comm, schedule)`` signatures
for external callers; in-repo code routes through an engine instance.

All functions run inside a ``shard_map`` body.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax
from jax.lax import axis_size

from repro.comm.engine import CollectiveEngine
from repro.comm.topology import ring_perm
from repro.comm.types import CommunicationType, comm_type


def axis_index(axis: str):
    return lax.axis_index(axis)


def _engine(comm, schedule: str) -> CollectiveEngine:
    return CollectiveEngine(comm=comm_type(comm), schedule=schedule)


# ---------------------------------------------------------------------------
# ring primitives (b_eff pattern)
# ---------------------------------------------------------------------------


def ring_shift(x: jnp.ndarray, axis: str, shift: int = 1) -> jnp.ndarray:
    """Send ``x`` to the neighbor ``shift`` hops along the ring; receive the
    buffer from the opposite neighbor. One circuit-switched hop."""
    n = axis_size(axis)
    return lax.ppermute(x, axis, ring_perm(n, shift))


def ring_exchange_bidir(x_fwd: jnp.ndarray, x_bwd: jnp.ndarray, axis: str,
                        comm=CommunicationType.ICI_DIRECT):
    """Bidirectional neighbor exchange (the b_eff message pattern). Returns
    (recv_from_left, recv_from_right)."""
    return _engine(comm, "auto").ring_exchange(x_fwd, x_bwd, axis)


# ---------------------------------------------------------------------------
# broadcast along a torus row/column (HPL panel broadcast)
# ---------------------------------------------------------------------------


def ring_bcast(val: jnp.ndarray, axis: str, src, comm=CommunicationType.ICI_DIRECT,
               schedule: str = "chain") -> jnp.ndarray:
    """Broadcast ``val`` from rank ``src`` (traced scalar ok) along ``axis``
    with the named schedule (see :mod:`repro.comm.engine`)."""
    return _engine(comm, schedule).bcast(val, axis, src)


# ---------------------------------------------------------------------------
# all-to-all tile exchange (PTRANS / MoE dispatch pattern)
# ---------------------------------------------------------------------------


def all_to_all_tiles(x: jnp.ndarray, axis: str, *, split_axis: int,
                     concat_axis: int, comm=CommunicationType.ICI_DIRECT,
                     schedule: str = "native") -> jnp.ndarray:
    """Exchange tiles so rank i's j-th split lands on rank j; rank j
    concatenates received tiles ordered by source rank on ``concat_axis``."""
    return _engine(comm, schedule).all_to_all_tiles(
        x, axis, split_axis=split_axis, concat_axis=concat_axis)


def roll_with_axis(x: jnp.ndarray, shift, axis: int) -> jnp.ndarray:
    """jnp.roll with traced shift along ``axis``."""
    n = x.shape[axis]
    idx = (jnp.arange(n) - shift) % n
    return jnp.take(x, idx, axis=axis)


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def psum_schedule(x: jnp.ndarray, axis: str, comm=CommunicationType.ICI_DIRECT,
                  schedule: str = "native") -> jnp.ndarray:
    """All-reduce over ``axis`` with the named schedule."""
    return _engine(comm, schedule).allreduce(x, axis)
