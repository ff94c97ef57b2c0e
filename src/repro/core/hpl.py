"""HPL / LINPACK — distributed blocked right-looking LU on a 2-D torus
(paper §2.3, Figs. 4-8). HPL-AI ruleset: diagonally-dominant A, no pivoting;
only the LU factorization runs on the accelerators, the triangular solves
run on the host as the CPU reference step, and the reported error is the
normalized residual ||Ax - b|| / (n * ||b|| * eps).

Per iteration k (paper Fig. 4):
  1. the (k%P, k%P) device factorizes the diagonal block   [kernels/lu.py]
  2. the packed LU block is broadcast along its grid row and column
     (the paper's "network kernels" forwarding through the torus — here
     ``CollectiveEngine.bcast`` with the ``chain`` store-and-forward,
     ``native``, or torus-aware ``ring2d`` scatter/all-gather schedule)
  3. grid row k%P solves the Top panel (U_kj), grid column k%P the Left
     panel (L_ik)                                          [trsm kernels]
  4. panels are broadcast down/across the torus
  5. every device applies the trailing rank-b GEMM update on its local
     blocks                                                 [gemm_update]

The masks that restrict panels to i,j > k are *multiplicative* (zeroed rows/
columns), so the trailing update needs no selects — a zeroed panel row
contributes nothing, exactly like the paper's "blocks left/above need no
further processing".

Lookahead (paper Fig. 5/7 overlap) — ``lookahead=d`` (``True`` == 1) keeps
``d`` panel pipelines in flight: per iteration k, only the row/column strips
that iteration k+d's panels read are updated first (2d thin GEMMs applying
the d pending in-flight updates restricted to that band — the strip-update
schedule skips every band already covered by earlier strip passes), then
iteration k+d's diagonal factorization and row/column broadcasts are issued,
and only then is the bulk trailing GEMM of iteration k applied. The k+d
broadcasts depend solely on the strips, so XLA can interleave the
``chain``/``ring2d`` hops of up to d iterations with the bulk updates —
covering the broadcast latency of small blocks on large tori. The bulk GEMM
still covers the full local matrix (the strip work is redundant compute,
~2db/m of the update FLOPs), which keeps the factorization bit-identical to
eager mode for every d: every matrix element takes its value from the same
full-GEMM arithmetic; the strip GEMM sequence applied to the k+d band is
per-element identical to the same d full GEMMs restricted to the band; and
the k+d panels never read global row/column <= k+d-1 (masked), the only
entries whose values the pending write-backs would change. The depth can be
resolved from the cost model (``lookahead="auto"`` in :func:`run_hpl` →
:func:`repro.comm.autotune.choose_hpl_depth`).
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.comm.callsites import HPL_BLOCK, HPL_PANEL
from repro.comm.engine import CollectiveEngine
from repro.comm.types import CommunicationType
from repro.core.hpcc import BenchResult, register, timeit
from repro.core.models import hpl_flops
from repro.core.ptrans import distribute_cyclic, undistribute_cyclic
from repro.kernels.ops import (gemm_update, lu_factor_block,
                               trsm_lower_left, trsm_upper_right)
from repro.scopes import (HPL_FACTOR, HPL_STRIP, HPL_TRSM, HPL_UPDATE,
                          HPL_WRITEBACK)


# ---------------------------------------------------------------------------
# problem generation / validation (host side, like the paper)
# ---------------------------------------------------------------------------


def generate_system(n: int, seed: int = 7) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Diagonally dominant A (HPL-AI rule), x = ones, b = A @ x."""
    rng = np.random.default_rng(seed)
    a = rng.uniform(-0.5, 0.5, (n, n)).astype(np.float32)
    a[np.arange(n), np.arange(n)] += n
    x = np.ones((n,), np.float32)
    b = a @ x
    return a, x, b


def solve_from_lu(lu: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Host triangular solves L y = b, U x = y from the packed LU (SciPy on
    the host CPU; the triangles are read from ``lu`` directly)."""
    from scipy.linalg import solve_triangular
    y = solve_triangular(lu, b, lower=True, unit_diagonal=True)
    return solve_triangular(lu, y, lower=False)


def normalized_residual(a: np.ndarray, x: np.ndarray, b: np.ndarray) -> float:
    eps = np.finfo(np.float32).eps
    r = np.max(np.abs(a @ x - b))
    return float(r / (a.shape[0] * np.max(np.abs(b)) * eps))


# ---------------------------------------------------------------------------
# distributed factorization
# ---------------------------------------------------------------------------


def _panels(k, diag, row_panel, col_panel, *, pg: int, b: int,
            engine: CollectiveEngine, li_global, lj_global):
    """Factor the diagonal block and form + broadcast iteration ``k``'s U/L
    panels (paper Fig. 4 steps 1-4). ``diag``/``row_panel``/``col_panel`` are
    this device's local strips at local block index k // pg, already carrying
    the first k rank-b updates. Returns (lu_blk, u_panel, l_panel), all
    broadcast grid-wide."""
    pk = k % pg

    # 1. diagonal block (speculative on every device; selected by bcast)
    with jax.named_scope(HPL_FACTOR):
        lu_local = lu_factor_block(diag)
    lu_blk = engine.bcast(lu_local, "cols", pk, callsite=HPL_BLOCK)
    lu_blk = engine.bcast(lu_blk, "rows", pk, callsite=HPL_BLOCK)

    # 2. Top panel: U_kj = L_kk^{-1} A_kj on grid row pk, cols j > k
    with jax.named_scope(HPL_TRSM):
        u_panel = trsm_lower_left(lu_blk, row_panel)
        colmask = jnp.repeat(lj_global > k, b)  # (m,)
        u_panel = u_panel * colmask[None, :]
    u_panel = engine.bcast(u_panel, "rows", pk, callsite=HPL_PANEL)

    # 3. Left panel: L_ik = A_ik U_kk^{-1} on grid col pk, rows i > k
    with jax.named_scope(HPL_TRSM):
        l_panel = trsm_upper_right(lu_blk, col_panel)
        rowmask = jnp.repeat(li_global > k, b)
        l_panel = l_panel * rowmask[:, None]
    l_panel = engine.bcast(l_panel, "cols", pk, callsite=HPL_PANEL)
    return lu_blk, u_panel, l_panel


def _update_writeback(k, a, lu_blk, u_panel, l_panel, *, pg: int, b: int,
                      lb: int, r, c, li_global, lj_global):
    """Apply iteration ``k``'s trailing rank-b GEMM over the full local
    matrix and write back the factored panels."""
    m = lb * b
    pk = k % pg
    lk = k // pg
    with jax.named_scope(HPL_WRITEBACK):
        colmask = jnp.repeat(lj_global > k, b)
        rowmask = jnp.repeat(li_global > k, b)

    # 4. trailing update: masks zero the factored rows/cols
    with jax.named_scope(HPL_UPDATE):
        a = gemm_update(a, l_panel, u_panel, alpha=-1.0, bm=b, bn=b)

    # 5. write back factored panels. The rank masks are folded INTO the
    # update values so every write is one slice-sized dynamic-update-slice —
    # a `where(r == pk, dus(a, ...), a)` select would touch the full local
    # matrix three times per iteration (measured as the second-largest HBM
    # term of the production HPL lowering, §Perf iteration C1).
    with jax.named_scope(HPL_WRITEBACK):
        old_row = lax.dynamic_slice(a, (lk * b, 0), (b, m))
        new_row = jnp.where(colmask[None, :] & (r == pk), u_panel, old_row)
        a = lax.dynamic_update_slice(a, new_row, (lk * b, 0))
        old_col = lax.dynamic_slice(a, (0, lk * b), (m, b))
        new_col = jnp.where(rowmask[:, None] & (c == pk), l_panel, old_col)
        a = lax.dynamic_update_slice(a, new_col, (0, lk * b))
        old_diag = lax.dynamic_slice(a, (lk * b, lk * b), (b, b))
        new_diag = jnp.where((r == pk) & (c == pk), lu_blk, old_diag)
        a = lax.dynamic_update_slice(a, new_diag, (lk * b, lk * b))
    return a


def _iteration(k, a, *, pg: int, b: int, lb: int, engine: CollectiveEngine,
               r, c, li_global, lj_global):
    """Eager iteration: factor+broadcast panels for k, then update."""
    m = lb * b
    lk = k // pg
    with jax.named_scope(HPL_FACTOR):
        diag = lax.dynamic_slice(a, (lk * b, lk * b), (b, b))
    with jax.named_scope(HPL_TRSM):
        row_panel = lax.dynamic_slice(a, (lk * b, 0), (b, m))
        col_panel = lax.dynamic_slice(a, (0, lk * b), (m, b))
    lu_blk, u_panel, l_panel = _panels(
        k, diag, row_panel, col_panel, pg=pg, b=b, engine=engine,
        li_global=li_global, lj_global=lj_global)
    return _update_writeback(k, a, lu_blk, u_panel, l_panel, pg=pg, b=b,
                             lb=lb, r=r, c=c, li_global=li_global,
                             lj_global=lj_global)


def _strip_panels(kidx, a, flight, *, pg: int, b: int, lb: int,
                  engine: CollectiveEngine, li_global, lj_global):
    """Form + broadcast iteration ``kidx``'s panels from thin strips of
    ``a``, first applying every pending in-flight update (the panel sets in
    ``flight``, oldest first) *restricted to the band* ``kidx`` reads — 2
    thin GEMMs per pending set. Bands of earlier in-flight iterations were
    strip-updated when their own panels were formed, so only this band's
    updates are (re)applied here — the strip-update schedule never revisits
    an already-updated band. ``kidx`` may be traced."""
    m = lb * b
    lk = kidx // pg
    with jax.named_scope(HPL_STRIP):
        row_strip = lax.dynamic_slice(a, (lk * b, 0), (b, m))
        col_strip = lax.dynamic_slice(a, (0, lk * b), (m, b))
        for lu_blk, u_panel, l_panel in flight:
            l_rows = lax.dynamic_slice(l_panel, (lk * b, 0), (b, b))
            row_strip = gemm_update(row_strip, l_rows, u_panel, alpha=-1.0,
                                    bm=b, bn=b)
            u_cols = lax.dynamic_slice(u_panel, (0, lk * b), (b, b))
            col_strip = gemm_update(col_strip, l_panel, u_cols, alpha=-1.0,
                                    bm=b, bn=b)
    with jax.named_scope(HPL_FACTOR):
        diag = lax.dynamic_slice(col_strip, (lk * b, 0), (b, b))
    return _panels(kidx, diag, row_strip, col_strip, pg=pg, b=b,
                   engine=engine, li_global=li_global, lj_global=lj_global)


def _iteration_lookahead(k, carry, *, pg: int, nb: int, b: int, lb: int,
                         depth: int, engine: CollectiveEngine, r, c,
                         li_global, lj_global):
    """Depth-d lookahead iteration (paper Fig. 5/7): the carry holds the
    ``depth`` in-flight panel sets for iterations k..k+d-1, already
    broadcast. Update only the strips iteration k+d reads (applying the d
    pending updates restricted to that band), issue k+d's factorization +
    broadcasts, THEN apply iteration k's bulk trailing GEMM — the broadcast
    hops depend only on the thin strip GEMMs, so XLA is free to overlap up
    to d iterations' broadcasts with the bulk updates.

    Bit-identity with eager mode, for every d: the bulk GEMM below still
    covers the full local matrix, so every element of ``a`` takes its value
    from exactly the eager arithmetic; the strip GEMM sequence is
    per-element identical to the same full GEMMs restricted to the strip
    (single k-block of b <= bk columns — asserted by
    tests/dist/test_overlap.py); and the k+d panels never read global
    row/column <= k+d-1 (masked multiplicatively), the only entries the
    pending write-backs would change."""
    a = carry[0]
    flight = tuple(carry[1:])  # depth triples (lu_blk, u_panel, l_panel)
    # iteration k+d's index, clamped near the end — the speculative panels
    # computed there are discarded with the carry
    kd = jnp.minimum(k + depth, nb - 1)

    # 1.-2. thin strip updates for the k+d band, then issue k+d's
    # factorization and row/column broadcasts now
    nxt = _strip_panels(kd, a, flight, pg=pg, b=b, lb=lb, engine=engine,
                        li_global=li_global, lj_global=lj_global)

    # 3. bulk trailing update + write back iteration k's factored panels
    # (the oldest in-flight set)
    a = _update_writeback(k, a, *flight[0], pg=pg, b=b, lb=lb, r=r, c=c,
                          li_global=li_global, lj_global=lj_global)
    return (a,) + flight[1:] + (nxt,)


def lookahead_depth(lookahead) -> int:
    """Normalize a ``lookahead`` argument to a pipeline depth: False/0 ->
    eager, True -> 1, an int d -> d. Negative depths fail fast here instead
    of as an opaque IndexError inside the factorization loop."""
    if lookahead is True:
        return 1
    if lookahead is False or lookahead is None:
        return 0
    depth = int(lookahead)
    if depth < 0:
        raise ValueError(f"lookahead depth must be >= 0, got {lookahead!r}")
    return depth


def _hpl_body(a_loc, *, pg: int, nb: int, b: int, engine: CollectiveEngine,
              lookahead=False):
    a = a_loc[0]
    lb = nb // pg
    r = lax.axis_index("rows")
    c = lax.axis_index("cols")
    li_global = jnp.arange(lb) * pg + r
    lj_global = jnp.arange(lb) * pg + c
    strip_kw = dict(pg=pg, b=b, lb=lb, engine=engine,
                    li_global=li_global, lj_global=lj_global)
    common = dict(r=r, c=c, **strip_kw)
    # no point carrying more panel sets than there are iterations
    depth = min(lookahead_depth(lookahead), nb)

    if depth:
        # prologue: fill the flight with iterations 0..d-1's panels, each
        # formed from strips carrying the pending earlier in-flight updates
        flight = []
        for j in range(depth):
            flight.append(_strip_panels(min(j, nb - 1), a, flight,
                                        **strip_kw))
        step = partial(_iteration_lookahead, nb=nb, depth=depth, **common)
        a = lax.fori_loop(0, nb, step, (a,) + tuple(flight))[0]
    else:
        step = partial(_iteration, **common)
        a = lax.fori_loop(0, nb, step, a)
    return a[None]


def make_factorize(mesh, *, pg: int, nb: int, b: int,
                   comm=CommunicationType.ICI_DIRECT, schedule: str = "auto",
                   lookahead=False, engine: CollectiveEngine = None):
    """``lookahead`` is a pipeline depth: False/0 eager, True/1 one panel
    set in flight, d >= 2 the depth-d pipeline."""
    engine = engine or CollectiveEngine.for_mesh(mesh, comm, schedule)
    spec = P(("rows", "cols"), None, None)
    fn = shard_map(
        partial(_hpl_body, pg=pg, nb=nb, b=b, engine=engine,
                lookahead=lookahead),
        mesh=mesh, in_specs=(spec,), out_specs=spec, check_vma=False)
    return jax.jit(fn)


@register("hpl")
def run_hpl(mesh, comm=CommunicationType.ICI_DIRECT, *, n: int = 512,
            b: int = 64, schedule: str = "auto", reps: int = 2,
            validate: bool = True, lookahead=False) -> BenchResult:
    """mesh axes ('rows', 'cols'), P = Q (paper's quadratic torus).

    ``lookahead`` runs the overlapped factorization (paper Fig. 5/7):
    ``True``/1 keeps one panel set in flight, an int d >= 2 the depth-d
    pipeline, ``"auto"`` resolves the depth from the cost model
    (:func:`repro.comm.autotune.choose_hpl_depth`). The LU output is
    bit-identical to eager mode under every bcast schedule at every depth.
    """
    pg = mesh.shape["rows"]
    assert mesh.shape["cols"] == pg, "paper requires a quadratic torus"
    nb = n // b
    assert nb % pg == 0, (n, b, pg)
    engine = CollectiveEngine.for_mesh(mesh, comm, schedule)

    m = (nb // pg) * b
    if lookahead == "auto":
        from repro.comm.autotune import choose_hpl_depth
        topo = engine.topology
        lookahead = choose_hpl_depth(
            b=b, m=m, axes=(topo.axis("rows"), topo.axis("cols")),
            model=engine.cost_model,
            # price the broadcasts on what THIS engine actually runs
            # (engine-wide overrides, HOST_STAGED forcing staged)
            resolve=lambda op, nbytes, ax, callsite: engine.schedule_for(
                op, nbytes=nbytes, axis=ax.name, callsite=callsite))
    depth = min(lookahead_depth(lookahead), nb)

    a, x_true, b_vec = generate_system(n)
    spec = NamedSharding(mesh, P(("rows", "cols"), None, None))
    a_sh = jax.device_put(distribute_cyclic(a, pg, b), spec)

    fact = make_factorize(mesh, pg=pg, nb=nb, b=b, engine=engine,
                          lookahead=depth)
    out, t = timeit(fact, a_sh, reps=reps)

    err = 0.0
    if validate:
        lu = undistribute_cyclic(np.asarray(out), pg, b)
        x = solve_from_lu(lu, b_vec)
        err = normalized_residual(a, x, b_vec)

    # resolved provenance: the *names the cost model picked* for both bcast
    # payloads — the b x b diagonal block and the dominant b x m row/column
    # panels — never the literal "auto"
    block_bytes = b * b * 4
    panel_bytes = b * m * 4
    resolved_block = engine.schedule_for("bcast", nbytes=block_bytes,
                                         axis="rows", callsite=HPL_BLOCK)
    resolved = engine.schedule_for("bcast", nbytes=panel_bytes, axis="rows",
                                   callsite=HPL_PANEL)
    return BenchResult(
        name="hpl", metric_name="GFLOP/s", metric=hpl_flops(n) / t / 1e9,
        error=err, times={"best": t},
        details={"n": n, "block": b, "grid": pg, "comm": engine.comm.value,
                 "schedule": resolved,
                 "schedule_block": resolved_block,
                 "schedule_panel": resolved,
                 "schedule_requested": engine.schedule,
                 "bcast_bytes": panel_bytes,
                 "block_bytes": block_bytes,
                 "lookahead": depth > 0,
                 "lookahead_depth": depth})
