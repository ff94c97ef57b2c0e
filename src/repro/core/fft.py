"""FFT — batched 1-D FFTs, local (legacy) and distributed (engine-routed).

**Local (legacy reference).** Embarrassingly parallel over devices; uses
XLA's FFT (the paper's FFT kernel is a legacy single-device design it did
not modify; DESIGN.md §9 records why no Pallas radix kernel is warranted).
Metric: 5 N log2 N FLOPs per 1-D FFT.

**Distributed (pencil decomposition).** The HPCC-adaptation work (Meyer et
al., arXiv:2004.11059) frames FFT as the all-to-all-bandwidth corner of the
suite: a signal too large for one device is pencil-decomposed and the
global transpose dominates. Here the input batch is sharded along the
*signal* axis (each device holds an ``(batch, n/P)`` pencil) and the
transform rides the :class:`~repro.comm.engine.CollectiveEngine`:

1. ``all_to_all_tiles`` under the ``fft.transpose`` tag re-lays the pencils
   out so each device holds ``batch/P`` *complete* signals;
2. the local transform is literally ``jnp.fft.fft`` over those full
   signals — which is what makes the distributed output **bitwise equal**
   to ``jnp.fft.fft`` applied at the same per-rank block shape, for every
   schedule × chunking (XLA's FFT is shape-deterministic but not
   row-independent across batch sizes, so the monolithic full-batch
   transform agrees to float32 FFT accuracy rather than in final bits);
3. the inverse exchange (tile axes swapped — the engine's a2a round-trip
   guarantee) restores the signal-sharded layout.

Why ``all_to_all_tiles`` and not ``grid_transpose``: the PTRANS-style
``grid_transpose`` partner exchange is only defined on square P=Q rank
grids (4 of 8 devices idle on the benchmark ring) and any 2-D block layout
shards *both* axes, so no rank ever holds a complete signal and the local
compute could not be ``jnp.fft.fft`` — bit-equivalence would be lost to
twiddle-factor reassociation. The layout-shuffle transpose above is the
1-D ring sibling of PTRANS's 2-D exchange; ``engine.pipelined`` strips the
per-signal frequency axis so chunk i's local FFT input lands while chunk
i+1 is on the wire.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.comm.callsites import FFT_TRANSPOSE
from repro.comm.engine import CollectiveEngine
from repro.comm.types import CommunicationType
from repro.core.hpcc import BenchResult, register, timeit

CALLSITE = FFT_TRANSPOSE  # tuning-table tag for both pencil exchanges


@register("fft")
def run_fft(mesh, comm=CommunicationType.ICI_DIRECT, *, log_size: int = 12,
            batch_per_device: int = 64, reps: int = 3) -> BenchResult:
    n_dev = mesh.devices.size
    n = 1 << log_size
    batch = batch_per_device * n_dev
    key = jax.random.PRNGKey(0)
    x = (jax.random.normal(key, (batch, n), jnp.float32)
         + 1j * jax.random.normal(jax.random.PRNGKey(1), (batch, n), jnp.float32))
    x = jax.device_put(x.astype(jnp.complex64), NamedSharding(mesh, P("x", None)))

    fn = jax.jit(shard_map(lambda a: jnp.fft.fft(a, axis=-1), mesh=mesh,
                           in_specs=P("x", None), out_specs=P("x", None)))
    out, t = timeit(fn, x, reps=reps)

    # validate the FULL output (an earlier revision checked only the first
    # two rows — a sharding bug on any later device shard went unseen)
    ref = np.fft.fft(np.asarray(x), axis=-1)
    err = float(np.max(np.abs(np.asarray(out) - ref)) / np.max(np.abs(ref)))

    flops = 5.0 * n * math.log2(n) * batch
    return BenchResult(
        name="fft", metric_name="GFLOP/s", metric=flops / t / 1e9, error=err,
        times={"best": t},
        details={"log_size": log_size, "batch": batch, "devices": n_dev})


# ---------------------------------------------------------------------------
# distributed pencil FFT (engine-routed global transpose)
# ---------------------------------------------------------------------------


def _fft_dist_body(x_loc, *, engine: CollectiveEngine, nchunks: int = 1):
    # x_loc (B, ns): all batch rows, this rank's signal pencil
    buf = x_loc[:, None, :]  # (B, 1, ns) — tile dim for the exchange

    def exchange(b, tile_split, tile_concat):
        # gather (0 -> 1): rank r's batch-tile j -> rank j, concat over
        # sources = (B/P, P, ns): B/P complete signals in P pencil segments.
        # scatter (1 -> 0): tile axes swapped — the engine's exact-inverse
        # round-trip guarantee. nchunks > 1 strips the per-signal frequency
        # axis (axis 2), which rides through untouched, so chunking is
        # bitwise-free.
        if nchunks <= 1:
            return engine.all_to_all_tiles(b, "x", split_axis=tile_split,
                                           concat_axis=tile_concat,
                                           callsite=CALLSITE)
        return engine.pipelined("all_to_all_tiles", b, "x", nchunks=nchunks,
                                split_axis=2, concat_axis=2,
                                tile_split_axis=tile_split,
                                tile_concat_axis=tile_concat,
                                callsite=CALLSITE)

    gathered = exchange(buf, 0, 1)             # (B/P, P, ns)
    full = gathered.reshape(gathered.shape[0], -1)  # (B/P, n) full signals
    spec = jnp.fft.fft(full, axis=-1)          # the reference transform
    spec = spec.reshape(gathered.shape)
    out = exchange(spec, 1, 0)                 # (B, 1, ns)
    return out[:, 0, :]


def make_dist_step(mesh, engine: CollectiveEngine, *, nchunks: int = 1):
    """Jitted pencil FFT: input/output sharded along the signal axis
    (``P(None, 'x')``); both global transposes ride ``fft.transpose``."""
    fn = shard_map(
        partial(_fft_dist_body, engine=engine, nchunks=nchunks),
        mesh=mesh, in_specs=P(None, "x"), out_specs=P(None, "x"),
        check_vma=False)
    return jax.jit(fn)


@register("fft_dist")
def run_fft_dist(mesh, comm=CommunicationType.ICI_DIRECT, *,
                 log_size: int = 12, batch_per_device: int = 64,
                 reps: int = 3, schedule: str = "auto",
                 nchunks="auto") -> BenchResult:
    """Pencil-decomposed distributed FFT over the ``x`` ring. The signal
    axis is sharded; the engine's ``fft.transpose`` exchanges localize full
    signals, so the output is bitwise equal to ``jnp.fft.fft`` at the
    per-rank block shape on every schedule × chunking (``error`` is the
    full-output relative error vs ``np.fft.fft``)."""
    n_dev = mesh.devices.size
    n = 1 << log_size
    batch = batch_per_device * n_dev
    if batch % n_dev:
        raise ValueError(f"batch {batch} not divisible by {n_dev} devices")
    if n % n_dev:
        raise ValueError(
            f"signal length 2**{log_size} = {n} not divisible by "
            f"{n_dev} devices (pencil decomposition)")
    engine = CollectiveEngine.for_mesh(mesh, comm, schedule)

    key = jax.random.PRNGKey(0)
    x = (jax.random.normal(key, (batch, n), jnp.float32)
         + 1j * jax.random.normal(jax.random.PRNGKey(1), (batch, n),
                                  jnp.float32))
    x = x.astype(jnp.complex64)
    x_sh = jax.device_put(x, NamedSharding(mesh, P(None, "x")))

    payload = batch * (n // n_dev) * 8  # per-rank (B, 1, ns) complex64
    nchunks_requested = nchunks
    if nchunks == "auto":
        nchunks = engine.pipeline_chunks("all_to_all_tiles", nbytes=payload,
                                         axis="x", callsite=CALLSITE)
    nchunks = max(int(nchunks), 1)

    step = make_dist_step(mesh, engine, nchunks=nchunks)
    out, t = timeit(step, x_sh, reps=reps)

    ref = np.fft.fft(np.asarray(x), axis=-1)
    err = float(np.max(np.abs(np.asarray(out) - ref)) / np.max(np.abs(ref)))

    flops = 5.0 * n * math.log2(n) * batch
    resolved = engine.schedule_for("all_to_all_tiles", nbytes=payload,
                                   axis="x", callsite=CALLSITE)
    return BenchResult(
        name="fft_dist", metric_name="GFLOP/s", metric=flops / t / 1e9,
        error=err, times={"best": t},
        details={"log_size": log_size, "batch": batch, "devices": n_dev,
                 "comm": engine.comm.value, "schedule": resolved,
                 "schedule_requested": engine.schedule,
                 "nchunks": nchunks,
                 "nchunks_requested": nchunks_requested,
                 "exchange_bytes": payload})
