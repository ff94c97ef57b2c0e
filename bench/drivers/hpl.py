"""HPL: HPCC LINPACK under the HPL-AI rule (Meyer et al., arXiv:2202.13995
§2.3), through ``repro.core.hpl.make_factorize`` with the program's
defaults (``schedule="auto"``, eager: no lookahead, as ``run_hpl`` runs).

Data: each chip's local block-cyclic matrix is made on the device from the
seed under the HPL-AI rule: entries U(-0.5, 0.5), and +N on every global
diagonal entry (diagonally dominant, so no pivoting is needed).

Check, on the host in float64, of the whole packed LU of the last timed
call, gathered from every chip (``bench.layout.gather``: each stack copied
off the devices once, into its global matrix; the host work spread over
its cores, ``bench.layout.each``):

- ``resid``: HPL's normalized residual ||A x - b||_inf /
  (eps * (||A||_inf ||x||_inf + ||b||_inf) * N) of the solve through the LU,
  with b = A @ ones, against HPL's own threshold of 16;
- ``berr``: the componentwise backward error max |A - L U| / (|L| |U|) on a
  grid of rows and columns drawn from the seed from every chip's blocks. The
  residual is dominated by the float32 rounding of the O(N) diagonal, so it
  cannot see a lower-precision panel solve or update; ``berr`` can.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from bench.clock import phase
from bench.layout import each, gather, global_blocks, torus_mesh, torus_size

size = torus_size
mesh = torus_mesh
# rows and columns of the sampled LU-product grid, spread over the grid rows
# and columns of the torus
BERR_SAMPLES = 128


def nominal_flops(n: int) -> float:
    """HPL's nominal work per factorization, 2/3 N^3 (the HPL-AI rule's
    count; copied from the program's ``core.models.hpl_flops``)."""
    return 2.0 * n ** 3 / 3.0


def end_to_end(cell, calls: int, seconds: float) -> dict:
    """``hpl_gflops``: nominal work of ``calls`` factorizations over
    ``seconds``, gaps between calls included."""
    return {"hpl_gflops": calls * nominal_flops(cell.size.n) / seconds / 1e9}


def required_update_flops(n: int, b: int, pg: int, r: int, c: int) -> float:
    """Trailing-update work the algorithm requires on device (r, c) for one
    factorization: sum over k of 2 b (local rows of global block > k) b
    (local columns of global block > k). Tiles of finished panels are not
    counted, whatever the kernel launches over them."""
    nb = n // b
    gi = global_blocks(pg, nb // pg, r)
    gj = global_blocks(pg, nb // pg, c)
    k = np.arange(nb)[:, None]
    rows = (gi[None, :] > k).sum(1) * b
    cols = (gj[None, :] > k).sum(1) * b
    return float(np.sum(2.0 * b * rows * cols))


def required_update_flops_total(n: int, b: int) -> float:
    """Closed form of the sum over all devices: sum_k 2 b (N - (k+1) b)^2."""
    nb = n // b
    return 2.0 * b ** 3 * (nb - 1) * nb * (2 * nb - 1) / 6.0


def masked_update_flops(n: int, b: int, pg: int) -> float:
    """What a trailing update over the whole local matrix in every
    iteration launches on one device: nb * 2 b m^2."""
    m = n // pg
    return float((n // b) * 2 * b * m * m)


# ---------------------------------------------------------------------------
# the cell's data and the program's entry
# ---------------------------------------------------------------------------


@dataclass
class State:
    n: int
    b: int
    pg: int
    seed: int
    limits: dict
    a: object = None           # device (P*P, m, m) block-cyclic matrix
    a_host: object = None      # the global matrix on the host, once read
    fact: object = None        # the program's jitted factorization
    info: dict = field(default_factory=dict)


def make_matrix(key, n: int, pg: int, sharding):
    """The HPL-AI matrix in the entry's layout, made on the devices."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    m = n // pg
    shape = (pg * pg, m, m)

    def gen(key):
        u = jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)
        d = lax.broadcasted_iota(jnp.int32, shape, 0)
        i = lax.broadcasted_iota(jnp.int32, shape, 1)
        j = lax.broadcasted_iota(jnp.int32, shape, 2)
        diag = (d // pg == d % pg) & (i == j)
        return u + jnp.where(diag, jnp.float32(n), jnp.float32(0))

    return jax.jit(gen, out_shardings=sharding)(key)


def setup(cell, mesh, key, seed: int) -> State:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.comm.callsites import HPL_BLOCK, HPL_PANEL
    from repro.comm.engine import CollectiveEngine
    from repro.core.hpl import make_factorize

    n, b, pg = cell.size.n, cell.size.b, cell.size.grid
    st = State(n=n, b=b, pg=pg, seed=seed, limits=cell.config["checks"])
    spec = NamedSharding(mesh, P(("rows", "cols"), None, None))
    st.a = make_matrix(key, n, pg, spec)
    st.fact = make_factorize(mesh, pg=pg, nb=n // b, b=b)
    # what "auto" resolves to for the two broadcast payloads, asked of an
    # engine built as make_factorize builds its own
    eng = CollectiveEngine.for_mesh(mesh)
    m = n // pg
    st.info = {
        "schedule_block": eng.schedule_for("bcast", nbytes=b * b * 4,
                                           axis="rows", callsite=HPL_BLOCK),
        "schedule_panel": eng.schedule_for("bcast", nbytes=b * m * 4,
                                           axis="rows", callsite=HPL_PANEL),
        "lookahead": 0}
    return st


def call(st: State):
    return st.fact(st.a)


def host_matrix(st: State) -> np.ndarray:
    if st.a_host is None:
        st.a_host = gather(st.a, st.pg, st.b)
    return st.a_host


def check(st: State, out) -> dict:
    """The compared numbers of the program's output, each with its limit."""
    with phase("check.copy"):
        lu = gather(out, st.pg, st.b)
        a = host_matrix(st)
    return verdict(a, lu, st)


# ---------------------------------------------------------------------------
# the reference check (host, float64)
# ---------------------------------------------------------------------------


def _rows_per_class(n: int, b: int, pg: int, rng, k: int) -> np.ndarray:
    """k global indices, k / P from each residue class of the block index,
    so that every grid row (column) of the torus is sampled."""
    idx = np.arange(n)
    out = [rng.choice(idx[(idx // b) % pg == r], k // pg, replace=False)
           for r in range(pg)]
    return np.sort(np.concatenate(out))


def _matvec64(x: np.ndarray, v: np.ndarray, rows: int = 32) -> np.ndarray:
    """``x.astype(np.float64) @ v``, ``rows`` rows at a time through one
    small float64 buffer, so no float64 copy of ``x`` is made."""
    out = np.empty(x.shape[0])
    buf = np.empty((rows, x.shape[1]))
    for i in range(0, x.shape[0], rows):
        part = buf[:min(rows, x.shape[0] - i)]
        part[...] = x[i:i + rows]
        out[i:i + rows] = part @ v
    return out


def _solve(lu: np.ndarray, rhs: np.ndarray, lower: bool,
           step: int = 2048) -> np.ndarray:
    """Block substitution through the unit-lower (``lower``) or upper
    triangle of the float32 ``lu``, in float64 one block row at a time."""
    from scipy.linalg import solve_triangular

    n = lu.shape[0]
    y = rhs.copy()
    starts = range(0, n, step) if lower else range((n - 1) // step * step,
                                                   -1, -step)
    for i in starts:
        j = min(i + step, n)
        if lower and i:
            y[i:j] -= _matvec64(lu[i:j, :i], y[:i])
        elif not lower and j < n:
            y[i:j] -= _matvec64(lu[i:j, j:], y[j:])
        y[i:j] = solve_triangular(lu[i:j, i:j].astype(np.float64), y[i:j],
                                  lower=lower, unit_diagonal=lower,
                                  check_finite=False)
    return y


def residual(a: np.ndarray, lu: np.ndarray, step: int = 2048) -> float:
    """HPL's normalized residual of the solve of A x = b, b = A @ ones in
    float64, through the packed unit-lower L and upper U. The exact
    solution is ones, so A x - b = A (x - 1), which a float32 product
    gives to about 1e-6 of itself."""
    from scipy.linalg import LinAlgError

    n = a.shape[0]
    rows = 64             # a task's rows: a small |A| temporary, reused

    def sums(i):
        blk = a[i:i + rows]
        return (blk.sum(1, dtype=np.float64),
                float(np.abs(blk).sum(1, dtype=np.float64).max()))

    parts = each(sums, range(0, n, rows))
    rhs = np.concatenate([p[0] for p in parts])
    a_norm = max([0.0] + [p[1] for p in parts])
    with np.errstate(all="ignore"):
        try:
            x = _solve(lu, _solve(lu, rhs, lower=True), lower=False)
        except (LinAlgError, ValueError):
            return float("inf")
        if not np.all(np.isfinite(x)):
            return float("inf")
        delta = (x - 1.0).astype(np.float32)
        r = np.concatenate([a[i:i + step] @ delta for i in range(0, n, step)])
        eps = float(np.finfo(np.float32).eps)
        denom = eps * (a_norm * np.abs(x).max() + np.abs(rhs).max()) * n
        res = float(np.abs(r).max() / denom)
    return res if np.isfinite(res) else float("inf")


def backward_error(a: np.ndarray, lu: np.ndarray, rows, cols) -> float:
    """max over the sampled grid of |A - L U| / (|L| |U|), float64."""
    n = a.shape[0]
    t = np.arange(n)
    lr = lu[rows, :].astype(np.float64)
    lr = np.where(t[None, :] < rows[:, None], lr, 0.0)
    lr[np.arange(len(rows)), rows] = 1.0
    uc = lu[:, cols].astype(np.float64)
    uc = np.where(t[:, None] <= cols[None, :], uc, 0.0)
    err = np.abs(a[np.ix_(rows, cols)].astype(np.float64) - lr @ uc)
    scale = np.abs(lr) @ np.abs(uc)
    with np.errstate(divide="ignore", invalid="ignore"):
        ratio = np.where(scale > 0, err / scale, np.where(err > 0, np.inf, 0))
    val = float(np.max(ratio))
    return val if np.isfinite(val) else float("inf")


def verdict(a: np.ndarray, lu: np.ndarray, st: State) -> dict:
    rng = np.random.default_rng([st.seed, 0x4850])
    rows = _rows_per_class(st.n, st.b, st.pg, rng, BERR_SAMPLES)
    cols = _rows_per_class(st.n, st.b, st.pg, rng, BERR_SAMPLES)
    with phase("check.resid"):
        resid = residual(a, lu)
    with phase("check.berr"):
        berr = backward_error(a, lu, rows, cols)
    return {"resid": (resid, st.limits["resid"]),
            "berr": (berr, st.limits["berr"])}


# ---------------------------------------------------------------------------
# the control: a plain blocked LU, put in the program's place, with its
# products one precision below the configuration's float32 at HIGHEST
# ---------------------------------------------------------------------------


def _split(x):
    """x = hi + lo with both in bfloat16: hi is x rounded to the nearest
    bfloat16 (even on ties) in integer arithmetic on the float32 word, lo is
    the rest rounded to bfloat16. Rounding the bits, rather than converting
    to bfloat16 and back, keeps the TPU compiler from folding the round
    trip away (it did: the split then had lo = 0 and one bf16 pass)."""
    import jax.numpy as jnp
    from jax import lax
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    hi = lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000), jnp.float32)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def dot_high(x, y):
    """float32 product at ``Precision.HIGH``: three bf16 passes (hi*hi +
    hi*lo + lo*hi), written out so that it means the same on every
    backend."""
    import jax.numpy as jnp
    xh, xl = _split(x)
    yh, yl = _split(y)

    def d(p, q):
        return jnp.dot(p, q, preferred_element_type=jnp.float32)

    return d(xh, yh) + (d(xh, yl) + d(xl, yh))


def dot_highest(x, y):
    import jax.numpy as jnp
    from jax import lax
    return jnp.dot(x, y, precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _tri_inverse(t, lower: bool):
    """Inverse of the unit-lower (``lower``) or upper triangle of ``t``, by
    column-oriented substitution in float32 elementwise arithmetic."""
    import jax.numpy as jnp
    from jax import lax

    b = t.shape[0]
    i = lax.broadcasted_iota(jnp.int32, (b, b), 0)
    j = lax.broadcasted_iota(jnp.int32, (b, b), 1)
    if lower:
        tri = jnp.where(i > j, t, 0.0) + jnp.eye(b, dtype=t.dtype)
    else:
        tri = jnp.where(i <= j, t, 0.0)
    ks = jnp.arange(b) if lower else jnp.arange(b - 1, -1, -1)

    def body(s, x):
        k = ks[s]
        piv = tri[k, k]
        xk = x[k] / piv
        x = x.at[k].set(xk)
        col = tri[:, k]
        later = (jnp.arange(b) > k) if lower else (jnp.arange(b) < k)
        return x - jnp.where(later[:, None], col[:, None] * xk[None, :], 0.0)

    return lax.fori_loop(0, b, body, jnp.eye(b, dtype=t.dtype))


def _lu_unblocked(d):
    import jax.numpy as jnp
    from jax import lax

    b = d.shape[0]
    idx = jnp.arange(b)

    def body(k, d):
        l = d[:, k] / d[k, k]
        below = idx > k
        upd = jnp.where(below[:, None] & below[None, :],
                        l[:, None] * d[k][None, :], 0.0)
        return jnp.where(below[:, None] & (idx == k)[None, :], l[:, None],
                         d - upd)

    return lax.fori_loop(0, b, body, d)


def plain_lu(a, b: int, dot):
    """Right-looking blocked LU without pivoting of the global matrix,
    every product through ``dot``: panels as products with the inverted
    diagonal factors, the trailing update one block row at a time."""
    import jax.numpy as jnp
    from jax import lax

    n = a.shape[0]
    nb = n // b
    idx = jnp.arange(n)

    def outer(k, a):
        k0 = k * b
        d = _lu_unblocked(lax.dynamic_slice(a, (k0, k0), (b, b)))
        after = idx >= k0 + b
        row = lax.dynamic_slice(a, (k0, 0), (b, n))
        col = lax.dynamic_slice(a, (0, k0), (n, b))
        u_row = jnp.where(after[None, :], dot(_tri_inverse(d, True), row), 0)
        l_col = jnp.where(after[:, None], dot(col, _tri_inverse(d, False)), 0)

        def inner(i, a):
            blk = lax.dynamic_slice(a, (i * b, 0), (b, n))
            l_blk = lax.dynamic_slice(l_col, (i * b, 0), (b, b))
            return lax.dynamic_update_slice(a, blk - dot(l_blk, u_row),
                                            (i * b, 0))

        a = lax.fori_loop(k + 1, nb, inner, a)
        a = lax.dynamic_update_slice(
            a, jnp.where(after[None, :], u_row, row), (k0, 0))
        col = lax.dynamic_slice(a, (0, k0), (n, b))
        a = lax.dynamic_update_slice(
            a, jnp.where(after[:, None], l_col, col), (0, k0))
        return lax.dynamic_update_slice(a, d, (k0, k0))

    return lax.fori_loop(0, nb, outer, a)


def control(st: State, dot=dot_high) -> np.ndarray:
    """The plain LU of this cell's matrix on one chip: the global LU."""
    import jax
    from functools import partial

    a = jax.device_put(host_matrix(st), jax.devices()[0])
    st.a = None                # the program's copy leaves the chip first
    lu = jax.jit(partial(plain_lu, b=st.b, dot=dot), donate_argnums=0)(a)
    return np.asarray(jax.device_get(lu))


def reference(st: State) -> np.ndarray:
    """The same plain LU at the configuration's own precision."""
    return control(st, dot=dot_highest)


def control_check(st: State, lu: np.ndarray) -> dict:
    return verdict(host_matrix(st), lu, st)

