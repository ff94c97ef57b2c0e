"""PTRANS — distributed matrix transposition C = B + A^T (paper §2.2).

Blocks are distributed block-cyclically over a P x P grid (the paper's PQ
scheme, Fig. 3, with P = Q as the circuit-switched implementation requires).
Each device stores its local blocks packed into one (lb*b, lb*b) matrix;
because the distribution is symmetric, the *entire* communication is a
single exchange with the grid-transpose partner, and the local compute is
one full-matrix transpose-add — tile(lj,li)^T lands at (li,lj) for both the
block index level and the within-block level at once.

The exchange routes through the :class:`~repro.comm.engine.CollectiveEngine`
``grid_transpose`` op:
* ``direct`` schedule under ICI_DIRECT — one ``ppermute`` over
  ('rows','cols') with the transpose permutation: a pure point-to-point
  circuit-switched exchange (paper §2.2.2).
* ``ring2d`` — dimension-ordered two-phase torus route (paper Fig. 8):
  row hops to the diagonal relay rank, then column hops to the transpose
  partner, using only physical torus links. Select with
  ``run_ptrans(..., schedule="ring2d")`` or ``--schedule ring2d`` /
  ``--sweep-schedules`` in the benchmark driver.
* ``staged`` (forced by HOST_STAGED) — all_gather over the full grid + local
  selection: every block transits the staging domain (paper §2.2.1 via
  PCIe+MPI).

Chunked (pipelined) exchange: ``run_ptrans(..., nchunks=S)`` splits the
local matrix into S row strips routed through
:meth:`~repro.comm.engine.CollectiveEngine.pipelined`, so the
``transpose_add`` of strip i overlaps the wire hops of strip i+1 — the
in-flight chunk pipeline the circuit-switched results rely on.
``nchunks="auto"`` (default) resolves S from the alpha-beta fill-cost model
(:func:`repro.comm.autotune.best_nchunks`); the result is bit-identical to
the monolithic exchange for every S (chunk boundaries only partition the
payload, and the transpose-add is elementwise).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.comm.callsites import PTRANS_EXCHANGE
from repro.comm.engine import CollectiveEngine
from repro.comm.types import CommunicationType
from repro.core.hpcc import BenchResult, register, timeit
from repro.kernels.ops import transpose_add
from repro.scopes import PTRANS_TRANSPOSE_ADD


# ---------------------------------------------------------------------------
# block-cyclic (de)distribution — shared with HPL
# ---------------------------------------------------------------------------


def distribute_cyclic(mat: np.ndarray, pg: int, b: int) -> np.ndarray:
    """(n, n) -> (pg*pg, m, m) stack of per-device local matrices. Global
    block (I, J) -> device (I%P, J%P), local tile (I//P, J//P)."""
    n = mat.shape[0]
    nb = n // b
    lb = nb // pg
    m = lb * b
    out = np.empty((pg * pg, m, m), mat.dtype)
    for gi in range(nb):
        for gj in range(nb):
            dev = (gi % pg) * pg + (gj % pg)
            li, lj = gi // pg, gj // pg
            out[dev, li * b:(li + 1) * b, lj * b:(lj + 1) * b] = \
                mat[gi * b:(gi + 1) * b, gj * b:(gj + 1) * b]
    return out


def undistribute_cyclic(shards: np.ndarray, pg: int, b: int) -> np.ndarray:
    nshards, m, _ = shards.shape
    lb = m // b
    nb = lb * pg
    n = nb * b
    out = np.empty((n, n), shards.dtype)
    for gi in range(nb):
        for gj in range(nb):
            dev = (gi % pg) * pg + (gj % pg)
            li, lj = gi // pg, gj // pg
            out[gi * b:(gi + 1) * b, gj * b:(gj + 1) * b] = \
                shards[dev, li * b:(li + 1) * b, lj * b:(lj + 1) * b]
    return out


# ---------------------------------------------------------------------------
# step
# ---------------------------------------------------------------------------


CALLSITE = PTRANS_EXCHANGE  # tuning-table tag for the partner exchange


def _ptrans_body(a_loc, b_loc, *, pg: int, engine: CollectiveEngine,
                 nchunks: int = 1):
    a_loc, b_loc = a_loc[0], b_loc[0]
    if nchunks <= 1:
        recv = engine.grid_transpose(a_loc, ("rows", "cols"), pg,
                                     callsite=CALLSITE)
        with jax.named_scope(PTRANS_TRANSPOSE_ADD):
            out = transpose_add(recv, b_loc)
        return out[None]

    # strip-wise pipeline: row strip i of A lands, its transpose-add writes
    # column strip i of C while strip i+1 is still on the wire
    def consume(strip, start):
        with jax.named_scope(PTRANS_TRANSPOSE_ADD):
            b_cols = lax.slice_in_dim(b_loc, start, start + strip.shape[0],
                                      axis=1)
            return transpose_add(strip, b_cols)

    out = engine.pipelined("grid_transpose", a_loc, ("rows", "cols"),
                           pg=pg, nchunks=nchunks, split_axis=0,
                           concat_axis=1, consume=consume,
                           callsite=CALLSITE)
    return out[None]


def make_step(mesh, pg: int, engine: CollectiveEngine, nchunks: int = 1):
    spec = P(("rows", "cols"), None, None)
    fn = shard_map(
        partial(_ptrans_body, pg=pg, engine=engine, nchunks=nchunks),
        mesh=mesh, in_specs=(spec, spec), out_specs=spec, check_vma=False)
    return jax.jit(fn)


@register("ptrans")
def run_ptrans(mesh, comm=CommunicationType.ICI_DIRECT, *, n: int = 1024,
               b: int = 128, reps: int = 3, validate: bool = True,
               schedule: str = "auto",
               nchunks="auto") -> BenchResult:
    """mesh must have axes ('rows', 'cols') with equal sizes (P = Q).

    ``nchunks`` pipelines the exchange into that many row strips (1 =
    monolithic); ``"auto"`` resolves the chunk count from the alpha-beta
    fill-cost model. Bit-identical output for every value."""
    pg = mesh.shape["rows"]
    assert mesh.shape["cols"] == pg, "paper requires P = Q"
    engine = CollectiveEngine.for_mesh(mesh, comm, schedule)
    rng = np.random.default_rng(42)
    a = rng.standard_normal((n, n), dtype=np.float32)
    bm = rng.standard_normal((n, n), dtype=np.float32)

    local_bytes = (n // pg) * (n // pg) * 4
    nchunks_requested = nchunks
    if nchunks == "auto":
        nchunks = engine.pipeline_chunks("grid_transpose",
                                         nbytes=local_bytes,
                                         axis=("rows", "cols"),
                                         callsite=CALLSITE)
    nchunks = max(int(nchunks), 1)

    spec = NamedSharding(mesh, P(("rows", "cols"), None, None))
    a_sh = jax.device_put(distribute_cyclic(a, pg, b), spec)
    b_sh = jax.device_put(distribute_cyclic(bm, pg, b), spec)

    step = make_step(mesh, pg, engine, nchunks=nchunks)
    out, t = timeit(step, a_sh, b_sh, reps=reps)

    err = 0.0
    if validate:
        c = undistribute_cyclic(np.asarray(out), pg, b)
        ref = bm + a.T
        err = float(np.max(np.abs(c - ref)))

    flops = float(n) * n  # paper: n^2 additions
    # resolved provenance: the cost model's pick for the actual per-device
    # exchange payload (the packed local matrix), never the literal "auto"
    resolved = engine.schedule_for("grid_transpose", nbytes=local_bytes,
                                   axis=("rows", "cols"), callsite=CALLSITE)
    return BenchResult(
        name="ptrans", metric_name="GFLOP/s", metric=flops / t / 1e9,
        error=err, times={"best": t},
        details={"n": n, "block": b, "grid": pg, "comm": engine.comm.value,
                 "schedule": resolved,
                 "schedule_requested": engine.schedule,
                 "nchunks": nchunks,
                 "nchunks_requested": nchunks_requested,
                 "exchange_bytes": local_bytes,
                 "bytes_exchanged": float(n) * n * 4})
