"""RandomAccess (GUPS) — paper §2.4's scalable redesign, two ways.

**Drop-local (legacy reference).** The paper replicates the RNG so every
FPGA generates (a partition of) the full update sequence and a
shift-register filter applies only the updates whose addresses fall into
the local shard. Reproduced here: every device runs ``rngs_per_device``
xorshift streams covering a disjoint slice of the global sequence, computes
all addresses, and scatters only in-range updates into its table shard
(out-of-range lanes are dropped — zero communication, like the paper).

**Engine-routed (distributed GUPS).** The HPCC-adaptation work (Meyer et
al., arXiv:2004.11059) treats RandomAccess as the latency corner of the
suite: real GUPS forwards every update to the rank that owns its address.
:func:`make_routed_step` does that through the
:class:`~repro.comm.engine.CollectiveEngine`: each rank buckets its
generated updates by owning rank into a fixed-capacity ``(n_dev, C, 2)``
int32 buffer of ``(local_index, value)`` pairs (unused lanes carry the
out-of-range sentinel, so nothing is ever dropped), one
``all_to_all_tiles`` exchange under the ``ra.updates`` callsite tag routes
bucket ``d`` to rank ``d``, and a single scatter-add applies everything
that arrived. ``nchunks > 1`` strips the capacity axis through
``engine.pipelined`` so the scatter of strip i overlaps strip i+1's wire
hops — bit-identical to the monolithic exchange for every chunking.

Deviation: HPCC uses XOR updates; JAX scatter has no XOR combinator, so we
use additive updates and validate by applying the inverse sequence
(int32 addition wraps but still commutes and inverts exactly, so collisions
cancel) — equivalent error semantics, stricter validation than the paper's
1% tolerance.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.comm.callsites import RA_UPDATES
from repro.comm.engine import CollectiveEngine
from repro.comm.types import CommunicationType
from repro.core.hpcc import BenchResult, register, timeit

# 32-bit variant of the HPCC LCG (JAX default disables x64; the generator is
# the same shift-xor structure on uint32 — period is shorter but far exceeds
# any benchmark run here). Documented deviation; table_log must be < 32.
POLY = np.uint32(0x7)

CALLSITE = RA_UPDATES  # tuning-table tag for the update-routing exchange


def _xorshift_step(x):
    """HPCC-style LCG: x_{i+1} = (x << 1) ^ (msb(x) ? POLY : 0)."""
    x = x.astype(jnp.uint32)
    shifted = x << jnp.uint32(1)
    high = (x >> jnp.uint32(31)) & jnp.uint32(1)
    return shifted ^ (high * jnp.uint32(POLY))


def _gen_updates(seed: jnp.ndarray, count: int) -> jnp.ndarray:
    def body(x, _):
        x = _xorshift_step(x)
        return x, x
    _, xs = lax.scan(body, seed, None, length=count)
    return xs


def _ra_body(table, seeds, *, updates_per_rng: int, table_log: int,
             sign: int):
    seeds = seeds[0]  # (rngs,) — leading device dim from P('x', None)
    local_size = table.shape[0]
    idx = lax.axis_index("x")
    lo = idx.astype(jnp.uint32) * jnp.uint32(local_size)

    vals = jax.vmap(lambda s: _gen_updates(s, updates_per_rng))(seeds)
    vals = vals.reshape(-1)
    addr = vals & jnp.uint32((1 << table_log) - 1)
    local = (addr - lo).astype(jnp.int32)
    in_range = (addr >= lo) & (addr < lo + jnp.uint32(local_size))
    local = jnp.where(in_range, local, local_size)  # dropped lane
    upd = jnp.where(in_range, vals.astype(jnp.int32) * sign, 0)
    table = table.at[local].add(upd, mode="drop")
    return table


def make_step(mesh, *, updates_per_rng: int, table_log: int, sign: int = 1):
    fn = shard_map(
        partial(_ra_body, updates_per_rng=updates_per_rng,
                table_log=table_log, sign=sign),
        mesh=mesh, in_specs=(P("x"), P("x", None)), out_specs=P("x"))
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# engine-routed distributed GUPS
# ---------------------------------------------------------------------------


def _bucket_updates(vals, *, table_log: int, local_size: int, n_dev: int,
                    sign: int):
    """Bucket a rank's raw xorshift values by owning rank.

    Returns an ``(n_dev, C, 2)`` int32 buffer (C = number of values): row
    ``d`` holds the ``(local_index, signed_value)`` pairs destined for rank
    ``d``, densely packed from slot 0; unused slots carry the sentinel
    local index ``local_size`` (out of range — the receiver's
    ``mode="drop"`` scatter ignores them) and value 0. C is the worst-case
    capacity (every update could target one rank), so no update is ever
    dropped — the routed path is exact.
    """
    c = vals.shape[0]
    addr = (vals & jnp.uint32((1 << table_log) - 1)).astype(jnp.int32)
    dest = addr // local_size
    local_idx = addr % local_size
    upd = vals.astype(jnp.int32) * sign

    def bucket(d):
        m = dest == d
        # dense slot within bucket d; non-members park at index C (dropped)
        slot = jnp.where(m, jnp.cumsum(m) - 1, c)
        loc = jnp.full((c,), local_size, jnp.int32).at[slot].set(
            local_idx, mode="drop")
        val = jnp.zeros((c,), jnp.int32).at[slot].set(upd, mode="drop")
        return loc, val

    locs, vals_out = jax.vmap(bucket)(jnp.arange(n_dev))
    return jnp.stack([locs, vals_out], axis=-1)


def _ra_routed_body(table, seeds, *, updates_per_rng: int, table_log: int,
                    n_dev: int, sign: int, engine: CollectiveEngine,
                    nchunks: int = 1):
    seeds = seeds[0]
    local_size = table.shape[0]

    vals = jax.vmap(lambda s: _gen_updates(s, updates_per_rng))(seeds)
    buf = _bucket_updates(vals.reshape(-1), table_log=table_log,
                          local_size=local_size, n_dev=n_dev, sign=sign)
    if nchunks <= 1:
        recv = engine.all_to_all_tiles(buf, "x", split_axis=0,
                                       concat_axis=0, callsite=CALLSITE)
    else:
        # strip the capacity axis: each landed strip's scatter could overlap
        # the next strip's wire hops; tile axes (0 -> 0) stay the exchange's
        recv = engine.pipelined("all_to_all_tiles", buf, "x",
                                nchunks=nchunks, split_axis=1,
                                concat_axis=1, tile_split_axis=0,
                                tile_concat_axis=0, callsite=CALLSITE)
    table = table.at[recv[..., 0].reshape(-1)].add(
        recv[..., 1].reshape(-1), mode="drop")
    return table


def make_routed_step(mesh, engine: CollectiveEngine, *,
                     updates_per_rng: int, table_log: int, sign: int = 1,
                     nchunks: int = 1):
    """Jitted engine-routed GUPS step: generate, bucket, exchange under
    ``ra.updates``, scatter-add. Unlike :func:`make_step` every generated
    update is applied (on its owning rank) — the distributed benchmark."""
    n_dev = mesh.devices.size
    fn = shard_map(
        partial(_ra_routed_body, updates_per_rng=updates_per_rng,
                table_log=table_log, n_dev=n_dev, sign=sign, engine=engine,
                nchunks=nchunks),
        mesh=mesh, in_specs=(P("x"), P("x", None)), out_specs=P("x"),
        check_vma=False)
    return jax.jit(fn)


def _make_table_and_seeds(mesh, *, table_log: int, rngs_per_device: int):
    n_dev = mesh.devices.size
    size = 1 << table_log
    if size % n_dev:
        raise ValueError(
            f"table size 2**{table_log} = {size} not divisible by "
            f"{n_dev} devices")
    rng = np.random.default_rng(3)
    init = rng.integers(1, 2 ** 30, size, dtype=np.int32)
    table = jax.device_put(jnp.asarray(init), NamedSharding(mesh, P("x")))
    # disjoint RNG seeds per (device, rng) — the paper's "sub-part of the
    # random number sequence" per replication
    seeds = rng.integers(1, 2 ** 30, (n_dev, rngs_per_device),
                         dtype=np.uint32)
    seeds_sh = jax.device_put(jnp.asarray(seeds),
                              NamedSharding(mesh, P("x", None)))
    return table, seeds_sh


@register("randomaccess")
def run_randomaccess(mesh, comm=CommunicationType.ICI_DIRECT, *,
                     table_log: int = 20, rngs_per_device: int = 4,
                     updates_per_rng: int = 4096, reps: int = 2) -> BenchResult:
    n_dev = mesh.devices.size
    size = 1 << table_log
    table, seeds_sh = _make_table_and_seeds(
        mesh, table_log=table_log, rngs_per_device=rngs_per_device)

    fwd = make_step(mesh, updates_per_rng=updates_per_rng,
                    table_log=table_log, sign=+1)
    inv = make_step(mesh, updates_per_rng=updates_per_rng,
                    table_log=table_log, sign=-1)

    out, t = timeit(fwd, table, seeds_sh, reps=reps)
    restored = inv(out, seeds_sh)
    err = float(jnp.sum(restored != table)) / size

    total_updates = float(n_dev * rngs_per_device * updates_per_rng)
    return BenchResult(
        name="randomaccess", metric_name="GUPS", metric=total_updates / t / 1e9,
        error=err, times={"best": t},
        details={"table_log": table_log, "devices": n_dev,
                 "rngs_per_device": rngs_per_device,
                 "updates": total_updates})


@register("randomaccess_dist")
def run_randomaccess_dist(mesh, comm=CommunicationType.ICI_DIRECT, *,
                          table_log: int = 20, rngs_per_device: int = 4,
                          updates_per_rng: int = 4096, reps: int = 2,
                          schedule: str = "auto",
                          nchunks="auto") -> BenchResult:
    """Engine-routed GUPS over the mesh's ``x`` ring: every update is
    forwarded to its owning rank through ``all_to_all_tiles`` under the
    ``ra.updates`` tag. Validated by exact inverse-sequence restore
    (``error`` is the fraction of mismatched table words — 0.0 on every
    schedule × chunking)."""
    n_dev = mesh.devices.size
    size = 1 << table_log
    engine = CollectiveEngine.for_mesh(mesh, comm, schedule)
    table, seeds_sh = _make_table_and_seeds(
        mesh, table_log=table_log, rngs_per_device=rngs_per_device)

    cap = rngs_per_device * updates_per_rng
    payload = n_dev * cap * 2 * 4  # (n_dev, C, 2) int32 per rank
    nchunks_requested = nchunks
    if nchunks == "auto":
        nchunks = engine.pipeline_chunks("all_to_all_tiles", nbytes=payload,
                                         axis="x", callsite=CALLSITE)
    nchunks = max(int(nchunks), 1)

    fwd = make_routed_step(mesh, engine, updates_per_rng=updates_per_rng,
                           table_log=table_log, sign=+1, nchunks=nchunks)
    inv = make_routed_step(mesh, engine, updates_per_rng=updates_per_rng,
                           table_log=table_log, sign=-1, nchunks=nchunks)

    out, t = timeit(fwd, table, seeds_sh, reps=reps)
    restored = inv(out, seeds_sh)
    err = float(jnp.sum(restored != table)) / size

    total_updates = float(n_dev * rngs_per_device * updates_per_rng)
    resolved = engine.schedule_for("all_to_all_tiles", nbytes=payload,
                                   axis="x", callsite=CALLSITE)
    return BenchResult(
        name="randomaccess_dist", metric_name="GUPS",
        metric=total_updates / t / 1e9, error=err, times={"best": t},
        details={"table_log": table_log, "devices": n_dev,
                 "rngs_per_device": rngs_per_device,
                 "updates": total_updates, "comm": engine.comm.value,
                 "schedule": resolved,
                 "schedule_requested": engine.schedule,
                 "nchunks": nchunks,
                 "nchunks_requested": nchunks_requested,
                 "exchange_bytes": payload})
