"""PTRANS: C = B + A^T on a P x P block-cyclic torus (Meyer et al.,
arXiv:2202.13995 §2.2), through the calls ``repro.core.ptrans.run_ptrans``
makes: ``CollectiveEngine.for_mesh``, ``engine.pipeline_chunks`` for
``nchunks="auto"``, then ``make_step``.

Data: A and B ~ N(0, 1) float32, made on the devices from the seed in the
entry's layout. Check, on the host: every element of C against B + A^T
computed in float32, which is exact (one rounding of one addition), so the
limit is 0. A, B and C come off the devices as global matrices
(``bench.layout.gather``), compared one tile at a time on the host's cores.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from bench.clock import phase
from bench.layout import each, gather, torus_mesh, torus_size

size = torus_size
mesh = torus_mesh
# rows and columns of the tiles the check compares at a time (1 MiB each)
TILE = 512


def nominal_flops(n: int) -> float:
    """n^2 additions per call, the paper's convention."""
    return float(n) * n


def end_to_end(cell, calls: int, seconds: float) -> dict:
    """``ptrans_gflops``: the additions of ``calls`` calls over ``seconds``,
    gaps between calls included."""
    return {"ptrans_gflops": calls * nominal_flops(cell.size.n) / seconds
            / 1e9}


def required_bytes(n: int, pg: int) -> float:
    """HBM bytes one device must move per call: read A, read B, write C,
    3 m^2 float32 words."""
    m = n // pg
    return 3.0 * m * m * 4


@dataclass
class State:
    n: int
    b: int
    pg: int
    seed: int
    limits: dict
    a: object = None
    bm: object = None
    step: object = None
    host: tuple = None         # global (A, B) on the host, once read
    info: dict = field(default_factory=dict)


def make_pair(key, n: int, pg: int, sharding):
    import jax
    import jax.numpy as jnp

    m = n // pg
    shape = (pg * pg, m, m)

    def gen(key):
        ka, kb = jax.random.split(key)
        return (jax.random.normal(ka, shape, jnp.float32),
                jax.random.normal(kb, shape, jnp.float32))

    return jax.jit(gen, out_shardings=(sharding, sharding))(key)


def setup(cell, mesh, key, seed: int) -> State:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.comm.callsites import PTRANS_EXCHANGE
    from repro.comm.engine import CollectiveEngine
    from repro.core.ptrans import make_step

    n, b, pg = cell.size.n, cell.size.b, cell.size.grid
    st = State(n=n, b=b, pg=pg, seed=seed, limits=cell.config["checks"])
    spec = NamedSharding(mesh, P(("rows", "cols"), None, None))
    st.a, st.bm = make_pair(key, n, pg, spec)
    engine = CollectiveEngine.for_mesh(mesh)
    local_bytes = (n // pg) ** 2 * 4
    nchunks = max(int(engine.pipeline_chunks(
        "grid_transpose", nbytes=local_bytes, axis=("rows", "cols"),
        callsite=PTRANS_EXCHANGE)), 1)
    st.step = make_step(mesh, pg, engine, nchunks=nchunks)
    st.info = {"schedule": engine.schedule_for(
        "grid_transpose", nbytes=local_bytes, axis=("rows", "cols"),
        callsite=PTRANS_EXCHANGE), "nchunks": nchunks}
    return st


def call(st: State):
    return st.step(st.a, st.bm)


def host_inputs(st: State):
    """Global (A, B) on the host, once read."""
    if st.host is None:
        st.host = (gather(st.a, st.pg, st.b), gather(st.bm, st.pg, st.b))
    return st.host


def max_abs_err(a, bm, c) -> float:
    """max |C - (B + A^T)| over every element of the global matrices, one
    row of tiles to a task, inf where it is not finite."""
    n = c.shape[0]

    def tile_row(i):
        return np.max([np.max(np.abs(
            c[i:i + TILE, j:j + TILE]
            - (bm[i:i + TILE, j:j + TILE] + a[j:j + TILE, i:i + TILE].T)))
            for j in range(0, n, TILE)])

    with np.errstate(invalid="ignore"):
        err = float(np.max(each(tile_row, range(0, n, TILE))))
    return err if np.isfinite(err) else float("inf")


def verdict(c: np.ndarray, st: State) -> dict:
    a, bm = host_inputs(st)
    with phase("check.max_abs_err"):
        err = max_abs_err(a, bm, c)
    return {"max_abs_err": (err, st.limits["max_abs_err"])}


def check(st: State, out) -> dict:
    with phase("check.copy"):
        c = gather(out, st.pg, st.b)
        host_inputs(st)                 # A and B, copied with C
    return verdict(c, st)


def control(st: State) -> np.ndarray:
    """B + A^T on one chip in bfloat16, the precision below float32."""
    import jax
    import jax.numpy as jnp

    a, bm = host_inputs(st)
    st.a = st.bm = None        # the program's copies leave the chip first
    dev = jax.devices()[0]

    def ref(a, bm):
        return (bm.astype(jnp.bfloat16) + a.T.astype(jnp.bfloat16)).astype(
            jnp.float32)

    c = jax.jit(ref)(jax.device_put(a, dev), jax.device_put(bm, dev))
    return np.asarray(jax.device_get(c))


def control_check(st: State, c: np.ndarray) -> dict:
    return verdict(c, st)
