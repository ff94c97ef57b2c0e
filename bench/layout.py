"""The block-cyclic P x P layout both HPCC entries take, kept with the
benchmark so that the check does not depend on the program's own
(de)distribution: global block (I, J) lives on device (I % P, J % P) at
local block (I // P, J // P), and device (r, c) is stack entry r * P + c.

Also the size and mesh of a cell on such a torus, which the HPL and PTRANS
drivers share: N from the mix, the block from the configuration, the grid
from the mix."""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Torus:
    n: int       # global matrix order
    b: int       # block size
    grid: int    # P of the P x P torus


def torus_size(config: dict, mix: dict, chips: int, tiny: bool) -> Torus:
    """The cell's sizes; ``--tiny`` takes the mix's toy ``n`` and ``b``."""
    size = dict(mix["tiny"]) if tiny else {}
    t = Torus(n=size.get("n", mix["n"]), b=size.get("b", config["b"]),
              grid=mix["grid"])
    if t.grid * t.grid != chips:
        raise ValueError(f"a {t.grid}x{t.grid} grid on {chips} chips")
    if t.n % (t.b * t.grid):
        raise ValueError(f"N={t.n} is not a multiple of b*P={t.b * t.grid}")
    return t


def torus_mesh(size: Torus, devices):
    """The ``('rows', 'cols')`` P x P mesh both entries are built over."""
    from repro.launch.mesh import make_mesh
    return make_mesh((size.grid, size.grid), ("rows", "cols"),
                     devices=devices)


def undistribute(shards: np.ndarray, pg: int, b: int) -> np.ndarray:
    """(P*P, m, m) stack of local matrices -> the (n, n) global matrix."""
    _, m, _ = shards.shape
    lb = m // b
    n = m * pg
    x = shards.reshape(pg, pg, lb, b, lb, b)        # r, c, li, bi, lj, bj
    return x.transpose(2, 0, 3, 4, 1, 5).reshape(n, n)


def distribute(mat: np.ndarray, pg: int, b: int) -> np.ndarray:
    """Inverse of :func:`undistribute`."""
    n = mat.shape[0]
    lb = n // b // pg
    m = lb * b
    x = mat.reshape(lb, pg, b, lb, pg, b)           # li, r, bi, lj, c, bj
    return x.transpose(1, 4, 0, 2, 3, 5).reshape(pg * pg, m, m)


def global_blocks(pg: int, lb: int, r: int) -> np.ndarray:
    """Global block indices of local blocks 0..lb-1 on grid row/col r."""
    return np.arange(lb) * pg + r
