"""Mesh construction. Functions, not module constants — importing this module
never touches jax device state."""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """The assigned production mesh: 16x16 (one v5e pod, 256 chips) or
    2x16x16 (two pods, 512 chips; the 'pod' axis is the DCN/host-staged
    domain — the paper's PCIe+MPI network)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with Auto axes: the repo's jit paths place arrays
    through sharding annotations (GSPMD), which the Explicit axes that
    ``jax.make_mesh`` defaults to would turn into sharding-in-types."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_local_mesh(axes=("data", "model")):
    """Best-effort mesh over however many local devices exist (tests/benches)."""
    n = len(jax.devices())
    if len(axes) == 1:
        return make_mesh((n,), tuple(axes))
    # squarest 2-way factorization
    a = int(np.floor(np.sqrt(n)))
    while n % a:
        a -= 1
    return make_mesh((n // a, a), tuple(axes))


def make_ring_mesh(name: str = "x"):
    n = len(jax.devices())
    return make_mesh((n,), (name,))


def make_torus_mesh(pg: int, names=("rows", "cols")):
    return make_mesh((pg, pg), tuple(names))
