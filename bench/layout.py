"""The block-cyclic P x P layout both HPCC entries take, kept with the
benchmark so that the check does not depend on the program's own
(de)distribution: global block (I, J) lives on device (I % P, J % P) at
local block (I // P, J // P), and device (r, c) is stack entry r * P + c.

Also the size and mesh of a cell on such a torus, which the HPL and PTRANS
drivers share: N from the mix, the block from the configuration, the grid
from the mix; ``gather``, which copies a stack off the devices into its
global matrix; and ``each``, which the checks run their host work on the
host's cores with."""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

# threads of the checks' host work: numpy leaves the interpreter's lock in
# its copies, casts and reductions, so they run side by side
MAX_THREADS = 16
# bytes of a slab: the local rows of every device that one transfer copies
# off the devices and one thread places on the host
SLAB_BYTES = 32 << 20


def each(fn, items) -> list:
    """``[fn(x) for x in items]``, on up to ``MAX_THREADS`` threads."""
    items = list(items)
    threads = min(MAX_THREADS, len(os.sched_getaffinity(0)), len(items))
    if threads <= 1:
        return [fn(x) for x in items]
    with ThreadPoolExecutor(threads) as pool:
        return list(pool.map(fn, items))


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


def _place(dst: np.ndarray, shards: np.ndarray, pg: int, b: int) -> None:
    """Write k local rows of every device, a (P*P, k, m) stack, into the
    (k * P, m * P) global rows they make up."""
    _, k, m = shards.shape
    x = shards.reshape(pg, pg, k // b, b, m // b, b)    # r, c, li, bi, lj, bj
    dst.reshape(k // b, pg, b, m // b, pg, b)[...] = x.transpose(2, 0, 3, 4,
                                                                 1, 5)


def undistribute(shards: np.ndarray, pg: int, b: int) -> np.ndarray:
    """(P*P, m, m) stack of local matrices -> the (n, n) global matrix."""
    _, m, _ = shards.shape
    out = np.empty((m * pg, m * pg), shards.dtype)
    _place(out, shards, pg, b)
    return out


def slab_rows(m: int, b: int, row_bytes: int) -> int:
    """Local rows to a slab: whole blocks, a divisor of ``m``, about
    ``SLAB_BYTES`` for rows of ``row_bytes`` bytes."""
    lb = m // b
    g = max(1, min(lb, SLAB_BYTES // (row_bytes * b)))
    while lb % g:
        g -= 1
    return g * b


def gather(x, pg: int, b: int) -> np.ndarray:
    """The (n, n) global matrix of the device stack ``x`` on the host:
    :func:`undistribute` of the stack, copied off the devices a slab of
    local rows at a time, each slab placed in its global rows by one of the
    host's threads. So the host's fresh pages are touched by many threads
    at once, and no second host copy of the stack is made."""
    import jax
    from jax import lax

    p2, m, mc = x.shape
    rows = slab_rows(m, b, p2 * mc * x.dtype.itemsize)
    take = jax.jit(lambda x, i: lax.dynamic_slice_in_dim(x, i, rows, 1)
                   ).lower(x, 0).compile()
    out = np.empty((m * pg, mc * pg), x.dtype)

    def slab(i):
        _place(out[i * pg:(i + rows) * pg], np.asarray(take(x, i)), pg, b)

    each(slab, range(0, m, rows))
    return out


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
